"""chip_smoke.py on the CPU: the dress rehearsal passes, the real mode refuses a
host without a TPU, the compile cache lands where it is placed, and a native
build that fails is an error — the tier-1 half of the chip contract (the other
half is ``python3 chip_smoke.py`` on the chip itself)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

_ROOT = Path(__file__).resolve().parent.parent
_PHASES = ["device", "streamed", "streamed", "fm_app", "serve", "pallas",
           "wlan_rx", "lora_gw", "multichip", "summary"]


def _run(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")     # conftest's 8 virtual devices
    return subprocess.run([sys.executable, str(_ROOT / "chip_smoke.py"), *args],
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=_ROOT)


def test_rehearsal_passes_every_phase_on_cpu():
    r = _run("--rehearse", "--devices", "4")
    assert r.returncode == 0, f"{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    lines = [json.loads(ln) for ln in r.stdout.splitlines() if ln.startswith("{")]
    # a rehearsal is not the chip check: it says so on its last line and on
    # every phase line, and prints no line a driver could take for the ok line
    assert lines[-1] == {
        "complete": False, "rehearse": True,
        "phases": ["device", "streamed", "fm_app", "serve", "pallas",
                   "wlan_rx", "lora_gw", "multichip"],
        "skipped": [], "device": {"platform": "cpu", "kind": "cpu",
                                  "count": lines[0]["device_count"]}}
    assert r.stdout.rstrip().splitlines()[-1].startswith('{"complete": false')
    assert not any(ln.startswith('{"ok"') for ln in r.stdout.splitlines())
    assert [ln["phase"] for ln in lines[:-1]] == _PHASES
    for ln in lines[:-1]:
        assert ln["rehearse"] is True
        assert ln["platform"] == "cpu" and ln["device_kind"] == "cpu"
        assert ln["jax"] and ln["jaxlib"] and ln["compile_cache_dir"]
        assert ln["ring"] in ("native", "portable")
        assert ln["compiles"] >= 0 and ln["compile_s"] >= 0
    for ln in lines[1:5]:                      # streamed x2, fm_app, serve
        assert ln["compiles_after_warmup"] == 0
    assert [ln["wire"] for ln in lines[1:3]] == ["f32", "sc16"]
    assert len(lines[5]["kernels"]) == 9
    assert lines[6]["packets_emitted"] == lines[6]["packets_sent"] > 8
    assert lines[6]["llr_err_max"] <= lines[6]["llr_tolerance"]
    assert lines[-2]["claim"] is None


def test_a_phase_subset_names_what_it_skipped_and_prints_no_ok_line():
    r = _run("--rehearse", "--phases", "streamed")
    assert r.returncode == 0, f"{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    last = json.loads(r.stdout.rstrip().splitlines()[-1])
    assert last["complete"] is False and "ok" not in last
    assert last["phases"] == ["device", "streamed"]
    assert last["skipped"] == ["fm_app", "serve", "pallas", "wlan_rx", "lora_gw"]


def test_without_rehearsal_a_cpu_host_is_refused():
    r = _run()
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout              # no result line without a chip


def test_compile_cache_env_var_wins_and_code_sets_nothing(monkeypatch):
    import jax

    from futuresdr_tpu.tpu.instance import ensure_compile_cache

    def refuse(*a, **k):
        raise AssertionError(f"jax.config.update{a} with the env var set")

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.setattr(jax.config, "update", refuse)
    assert ensure_compile_cache() == "/some/dir"


def test_compile_cache_default_is_one_directory_in_the_checkout(
        monkeypatch, tmp_path):
    from futuresdr_tpu.tpu.instance import ensure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = set()
    for cwd in (tmp_path, _ROOT / "tests"):
        monkeypatch.chdir(cwd)
        seen.add(ensure_compile_cache())
    assert seen == {str(_ROOT / ".jax_cache")}


def test_failed_native_build_raises(monkeypatch, tmp_path):
    from futuresdr_tpu.runtime.buffer import circular

    (tmp_path / "Makefile").write_text("all:\n\t@echo boom >&2; false\n")
    monkeypatch.setattr(circular, "_lib", None)
    monkeypatch.setattr(circular, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.delenv("FSDR_NO_NATIVE", raising=False)
    with pytest.raises(RuntimeError, match="(?s)native build failed.*boom"):
        circular.load_native()
    # the portable ring is chosen by FSDR_NO_NATIVE=1, never by a failure
    monkeypatch.setenv("FSDR_NO_NATIVE", "1")
    assert circular.load_native() is None and not circular.available()
