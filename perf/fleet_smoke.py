#!/usr/bin/env python
"""Fleet-plane smoke (check.sh gate, docs/observability.md "The fleet
plane"): three live control-port hosts over real sockets, kill one, the
pressure-routed admission plane shifts to the survivors.

Hard assertions, all on the REAL cross-host plane (the hosts are jax-free
control-port children — the single-host serving engine behind them is
covered by perf/serve_ab.py; this gate pays for the part no single-process
test sees: REST summaries, poller staleness, merged exposition and routed
failover across OS processes):

* **Readiness.** The FleetView aggregator reaches ``hosts_ready == 3``
  from a cold start within its own staleness budget.
* **Merged exposition.** ``merge_metrics`` over the live hosts yields a
  stably-ordered text where EVERY sample line carries a ``host=`` label —
  two back-to-back scrapes are line-for-line identical (the Grafana
  contract: panel queries must not churn on scrape order).
* **Pressure routing + failover.** The first admit lands on the
  least-pressure host; after SIGKILL of that host the view flips it
  stale → down (journal-ordered, at exactly ``fleet_down_errors``
  consecutive misses) and 100% of subsequent admits land on survivors.

Run: ``JAX_PLATFORMS=cpu python perf/fleet_smoke.py --smoke``
"""

import argparse
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

_CHILD = os.path.join(_ROOT, "tests", "_fleet_child.py")
PRESSURES = (0.1, 0.3, 0.5)
INTERVAL = 0.15


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_children(specs):
    """specs: [(port, pressure), ...] -> procs (READY line awaited)."""
    pypath = _ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")
    env = dict(os.environ, PYTHONPATH=pypath.rstrip(os.pathsep))
    procs = [subprocess.Popen(
        [sys.executable, _CHILD, str(port), str(pressure)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)
        for port, pressure in specs]
    deadline = time.monotonic() + 30
    for p, (port, _pr) in zip(procs, specs):
        seen = []
        while time.monotonic() < deadline:
            line = p.stdout.readline()     # log lines precede the marker
            seen.append(line)
            if "READY" in line or not line:
                break
        assert seen and "READY" in seen[-1], f"child {port} failed: {seen!r}"
    return procs


def _build():
    """3 children + a started FleetView + router over them."""
    from futuresdr_tpu.serve.router import AdmissionRouter
    from futuresdr_tpu.telemetry.fleet import FleetView
    specs = [(_free_port(), pr) for pr in PRESSURES]
    peers = [f"127.0.0.1:{port}" for port, _ in specs]
    procs = _spawn_children(specs)
    view = FleetView(peers, poll_interval=INTERVAL).start()
    router = AdmissionRouter(view, hysteresis=0.05)
    return procs, peers, view, router


def _wait_ready(view, n, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if len(view.ready_hosts()) >= n:
            return True
        time.sleep(INTERVAL / 3)
    return False


def _teardown(procs, view):
    view.stop()
    for p in procs:
        if p.poll() is None:
            p.kill()
        p.wait(timeout=10)


def smoke() -> int:
    from futuresdr_tpu.telemetry import journal as journal_mod
    procs, peers, view, router = _build()
    try:
        assert _wait_ready(view, 3), \
            f"fleet never reached 3 ready hosts: {view.hosts()}"
        snap = view.snapshot()
        assert snap["ready"] and snap["hosts_ready"] == 3, snap
        print(f"# fleet up: {snap['hosts_ready']} hosts ready, pressures "
              f"{[h['summary']['pressure'] for h in snap['hosts'].values()]}")

        # merged exposition: every sample host-labelled, scrape-stable
        m1, m2 = view.merged_metrics(), view.merged_metrics()
        samples = [ln for ln in m1.splitlines()
                   if ln and not ln.startswith("#")]
        assert samples, "merged exposition carries no samples"
        bad = [ln for ln in samples if 'host="' not in ln]
        assert not bad, f"unlabelled merged samples: {bad[:3]}"
        assert m1.splitlines() == m2.splitlines(), \
            "merged exposition not scrape-stable"
        print(f"# merged metrics: {len(samples)} samples, all host-labelled, "
              f"scrape-stable")

        # pressure routing: first admit lands on the least-pressure host
        first = router.admit("app", tenant="smoke")
        assert first["host"] == peers[0], first

        # kill the pick; the view flips it stale -> down (journal-ordered)
        j0 = journal_mod.journal().seq
        procs[0].send_signal(signal.SIGKILL)
        procs[0].wait(timeout=10)
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if view.hosts()[peers[0]]["state"] == "down":
                break
            time.sleep(INTERVAL / 3)
        assert view.hosts()[peers[0]]["state"] == "down", view.hosts()
        evs = [e for e in journal_mod.events(since=j0, cat="fleet")["events"]
               if e.get("host") == peers[0]]
        assert [e["event"] for e in evs][:2] == ["host-stale", "host-down"], \
            [e["event"] for e in evs]
        assert evs[1]["errors"] == view.down_errors, evs[1]

        # 100% of post-kill admits land on survivors, every one journaled
        targets = [router.admit("app", tenant=f"t{i}")["host"]
                   for i in range(10)]
        assert set(targets) <= {peers[1], peers[2]}, targets
        routed = [e for e in journal_mod.events(since=j0,
                                                cat="fleet")["events"]
                  if e["event"] == "route"]
        assert len(routed) >= 10 and \
            all(e["host"] != peers[0] for e in routed), routed
        print(f"# failover: {peers[0]} down after {evs[1]['errors']} misses, "
              f"10/10 admits to survivors")
        print("FLEET_SMOKE OK: 3 hosts, stable merged exposition, "
              "pressure-routed failover")
        return 0
    finally:
        _teardown(procs, view)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="run the check.sh smoke (hard asserts)")
    p.parse_args()
    return smoke()


if __name__ == "__main__":
    sys.exit(main())
