"""Every example script actually runs — end-to-end smoke in subprocesses.

The examples are the user's first contact with the framework; a bit-rotted
example is a worse advertisement than a missing one. Each runs with its
smallest useful workload in its own process (its own jax init, held to the CPU
by ``JAX_PLATFORMS=cpu``) and must exit 0 within the timeout.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

_ROOT = Path(__file__).resolve().parent.parent
_EXAMPLES = [
    ("cw_beacon.py", ["HI", "--wav", "{tmp}/cw.wav"]),
    ("lora_loopback.py", ["--frames", "2"]),
    ("m17_loopback.py", ["--frames", "1"]),
    ("rattlegram_loopback.py", ["--messages", "1", "--payload-size", "32"]),
    ("wlan_loopback.py", ["--frames", "2"]),
    ("zigbee_loopback.py", ["--frames", "2"]),
    ("modem_ota.py", ["hello"]),
    ("modem_ota.py", ["metadata in band", "--callsign", "N0CALL"]),
    ("adsb_rx.py", []),                      # synthesizes its own stream
    ("custom_routes.py", []),                # self-curls its extra REST routes
    ("file_trx.py", ["rx", "--out", "{tmp}/cap.cs8", "--samples", "50000"]),
    ("ssb_rx.py", ["--wav", "{tmp}/ssb.wav"]),   # self-validating loopback
    ("keyfob_rx.py", []),                        # tx → rx loopback, code checked
    ("keyfob_rx.py", ["tx", "--out", "{tmp}/burst.cf32"]),
    ("sharded_spectrum.py", ["--virtual-mesh", "--devices", "2", "--frames",
                             "2", "--frame-size", "16384"]),
]


@pytest.mark.parametrize("script,args", _EXAMPLES,
                         ids=[e[0].removesuffix(".py") for e in _EXAMPLES])
def test_example_runs(script, args, tmp_path):
    args = [a.format(tmp=tmp_path) for a in args]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(_ROOT) + os.pathsep + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, str(_ROOT / "examples" / script), *args],
                       capture_output=True, text=True, timeout=240, env=env,
                       cwd=_ROOT)
    assert r.returncode == 0, f"{script} failed:\n{r.stdout[-1500:]}\n{r.stderr[-1500:]}"
