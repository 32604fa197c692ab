"""The system under test, as the benchmark drives it: the compiler's
``CompileService.compile_many`` with one ``CompileRequest`` per call.

This is the only module that imports the program (``src/repro``), apart
from the counters the metric readers take from it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]

# settings of the program that would change what a cell measures
_PINNED_ENV = ("PFDNN_BACKEND", "PFDNN_PALLAS", "PFDNN_WORKERS",
               "PFDNN_STACK_LIVE", "PFDNN_LOCKCHECK")


def import_program():
    """Put the checkout's ``src/`` on the path and import the program;
    raises ImportError where it is not there."""
    for var in _PINNED_ENV:
        os.environ.pop(var, None)
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.core.backend  # noqa: F401
    import repro.service  # noqa: F401


def configure_compile_cache() -> str:
    """JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache`` unless ``$JAX_COMPILATION_CACHE_DIR`` names one."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    from repro.core.backend import configure_compile_cache as configure

    return configure()


class Compiler:
    """The program, set up for one cell: ``compile(request)`` is the call
    the window times."""

    def __init__(self, config: dict, backend: str):
        from repro.core import OrchestratorConfig
        from repro.hw.edge40nm import Edge40nmAccelerator

        self.acc = Edge40nmAccelerator(**config.get("accelerator", {}))
        self.cfg = OrchestratorConfig(policy=config["policy"],
                                      n_max_rails=config["n_max_rails"],
                                      backend=backend)
        self.backend = backend
        from repro.service import CompileService

        self._service = CompileService(self.acc)

    def program_request(self, req):
        from repro.perfmodel import LayerSpec
        from repro.service import CompileRequest, MinEnergy

        specs = [LayerSpec(**vars(layer)) for layer in req.layers()]
        cfg = self.cfg if req.cuts else \
            dataclasses.replace(self.cfg, warm_start=False)
        return CompileRequest(specs, cfg=cfg, network=req.label,
                              goal=MinEnergy(rate_hz=req.rate_hz))

    def compile(self, req):
        return self._service.compile_many([self.program_request(req)])[0]

    def close(self) -> None:
        if self._service is not None:
            self._service.close()
            self._service.store.clear()
            self._service = None


def device_dtypes() -> list:
    """The dtypes of the device arrays alive in this process (none
    where JAX was never imported)."""
    if "jax" not in sys.modules:
        return []
    import jax

    return [a.dtype for a in jax.live_arrays()]


def backend_counters(name: str) -> dict:
    """The jax backend's transfer and dispatch counters (zeros for a
    backend that keeps none)."""
    from repro.core.backend import get_backend

    return dict(getattr(get_backend(name), "io_stats", {}))


@contextlib.contextmanager
def no_device_x64():
    """The precision control: the jax backend's device programs run in
    float32 (its float64 scope switched off), the rest of the program as
    it is."""
    from repro.core.backend import JaxBackend

    saved = JaxBackend._x64
    JaxBackend._x64 = lambda self: contextlib.nullcontext()
    try:
        yield
    finally:
        JaxBackend._x64 = saved
