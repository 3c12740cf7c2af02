"""Predictor interface (paper F2/F3, §4.4.3, Listing 3).

The paper wraps each framework's C API behind three functions::

    ModelHandle   ModelLoad(OpenRequest)
    Error         ModelUnload(ModelHandle)
    PredictResponse Predict(ModelHandle, PredictRequest, PredictOptions)

Anything implementing the 3-function interface is a valid predictor — the
paper exposes FPGAs this way. Here the "frameworks" are JAX compute
backends (``ref`` pure-jnp vs ``pallas`` TPU kernels, and compiled AOT
executables per mesh); a predictor owns materialized weights + the compiled
step functions and hides everything else from the agent, keeping the agent
code backend-agnostic.
"""
from __future__ import annotations

import itertools
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

from .manifest import ModelManifest
from .tracing import NullTracer, Tracer, TraceLevel

_handles = itertools.count(1)


@dataclass
class OpenRequest:
    """Listing 4's OpenRequest: everything needed to load one predictor."""

    manifest: ModelManifest
    backend: str = "ref"
    batch_size: int = 1
    seq_len: int = 128
    mode: str = "serve"          # "serve" | "train"
    options: Dict[str, Any] = field(default_factory=dict)


@dataclass
class PredictorHandle:
    handle_id: int
    backend: str
    model_key: str
    state: Any = None            # backend-private (weights, compiled fns, caches)


class Predictor:
    """Abstract 3-function predictor. Subclass and register a factory."""

    name = "abstract"
    version = "1.0.0"

    def open(self, req: OpenRequest, tracer: Tracer) -> PredictorHandle:
        raise NotImplementedError

    def predict(
        self, handle: PredictorHandle, batch: Any, tracer: Tracer
    ) -> Any:
        raise NotImplementedError

    def close(self, handle: PredictorHandle) -> None:
        raise NotImplementedError


class CallablePredictor(Predictor):
    """Wrap plain callables as a predictor (the FPGA/ASIC story of §4.4.3:
    implementing the 3 functions is sufficient — no framework needed)."""

    def __init__(
        self,
        name: str,
        load_fn: Callable[[OpenRequest], Any],
        predict_fn: Callable[[Any, Any], Any],
        unload_fn: Optional[Callable[[Any], None]] = None,
        version: str = "1.0.0",
    ) -> None:
        self.name = name
        self.version = version
        self._load = load_fn
        self._predict = predict_fn
        self._unload = unload_fn

    def open(self, req: OpenRequest, tracer: Tracer) -> PredictorHandle:
        with tracer.span("model_load", TraceLevel.MODEL, backend=self.name):
            state = self._load(req)
        return PredictorHandle(
            handle_id=next(_handles),
            backend=self.name,
            model_key=req.manifest.key,
            state=state,
        )

    def predict(self, handle: PredictorHandle, batch: Any, tracer: Tracer) -> Any:
        with tracer.span("inference", TraceLevel.MODEL, backend=self.name):
            return self._predict(handle.state, batch)

    def close(self, handle: PredictorHandle) -> None:
        if self._unload is not None:
            self._unload(handle.state)
        handle.state = None


# --------------------------------------------------------------------------
# Predictor registry (the "adding frameworks" extension point, §4.6)
# --------------------------------------------------------------------------
_FACTORIES: Dict[str, Callable[[], Predictor]] = {}
_lock = threading.Lock()


def register_predictor(name: str, factory: Callable[[], Predictor]) -> None:
    with _lock:
        _FACTORIES[name] = factory


def make_predictor(name: str) -> Predictor:
    with _lock:
        try:
            factory = _FACTORIES[name]
        except KeyError:
            raise KeyError(
                f"no predictor backend {name!r}; registered: {sorted(_FACTORIES)}"
            )
    return factory()


def available_backends() -> list:
    with _lock:
        return sorted(_FACTORIES)


def _register_builtin() -> None:
    """Register the JAX model-zoo predictors (imported here, not at module
    top, to break the models -> core import cycle).  An import failure
    propagates: a platform without its backends is a broken install."""
    from ..models.predictor import JaxModelPredictor  # noqa: WPS433

    for backend in ("ref", "pallas"):
        if backend not in _FACTORIES:
            register_predictor(
                backend, lambda b=backend: JaxModelPredictor(kernel_backend=b)
            )


_register_builtin()
