"""Jax-free control-port child for the live fleet tests.

Runs one ControlPort with a duck-typed fake serving engine registered
under app "app" — enough surface for the fleet plane (``health()``,
``retry_after_s()``, ``credits.pressure()``, the slot table) and for REST
admissions, without paying the compute plane's jax import per child (the
control port and serve/api.py are deliberately jax-free; perf/fleet_smoke
covers the real-engine topology).

Usage: ``python -m tests._fleet_child <port> [pressure] [shed_level]``.
Prints ``READY`` once the port is listening, then parks.
"""

import os
import sys
import time


class _Credits:
    def __init__(self, p: float):
        self._p = float(p)

    def pressure(self) -> float:
        return self._p


class FakeEngine:
    """The lock-free subset of ServeEngine the fleet plane reads, plus
    ``admit`` for routed REST admissions."""

    def __init__(self, app: str, pressure: float = 0.0,
                 shed_level: int = 0, capacity: int = 64):
        from futuresdr_tpu.serve.slots import SlotTable
        self.app = app
        self.table = SlotTable(capacity)
        self.credits = _Credits(pressure)
        self.draining = False
        self.shed_level = int(shed_level)

    @property
    def capacity(self) -> int:
        return self.table.capacity

    def health(self) -> dict:
        return {"ready": True, "compiled": True, "draining": False,
                "drained": False, "shed_level": self.shed_level,
                "shed_rung": "ok" if not self.shed_level else "admission",
                "active": self.table.active,
                "capacity": self.table.capacity}

    def retry_after_s(self) -> int:
        return 1

    def frame_format(self) -> dict:
        return {"wire": "raw", "frame_dtype": "complex64",
                "frame_shape": [0], "full_scale": None}

    def admit(self, tenant: str = "default", sid=None):
        from futuresdr_tpu.serve.slots import Session
        s = Session(tenant, sid)
        self.table.admit(s)
        return s


class _Handle:
    def flowgraph_ids(self):
        return []

    def get_flowgraph(self, fg):
        return None


def main() -> None:
    port = int(sys.argv[1])
    pressure = float(sys.argv[2]) if len(sys.argv) > 2 else 0.0
    shed = int(sys.argv[3]) if len(sys.argv) > 3 else 0
    # fleet identity = the control-port address (what the aggregator polls)
    os.environ.setdefault("FUTURESDR_TPU_FLEET_HOST_ID",
                          f"127.0.0.1:{port}")
    from futuresdr_tpu.runtime.ctrl_port import ControlPort
    from futuresdr_tpu.serve import api as serve_api
    serve_api.register_app(FakeEngine("app", pressure, shed), "app")
    cp = ControlPort(_Handle(), bind=f"127.0.0.1:{port}")
    cp.start()
    print("READY", flush=True)
    while True:
        time.sleep(1)


if __name__ == "__main__":
    main()
