"""What both drivers need: the run's context, the model built from a
configuration file, the compile counter, the tracer's slice."""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import time
from typing import Any, Dict, List, Optional

from .. import reference, trace

# a new program was lowered, or the backend compiled one: neither may
# happen inside a measured window
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


@dataclasses.dataclass
class Context:
    """One run of one cell, as ``run.py`` hands it to a driver."""
    cell_name: str
    cell: Dict
    config: Dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_process: float            # time.perf_counter() when the process began
    scratch: str                # a directory inside the checkout
    device_kind: str

    def emit(self, event: str, **fields: Any) -> None:
        """An earlier line of standard output; never the last."""
        print(json.dumps({"event": event, **fields}), flush=True)


@dataclasses.dataclass
class Result:
    """What a driver hands back. ``end_to_end`` maps a metric's name to
    (value, unit); the remaining fields are what per-layer readers read."""
    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, tuple]
    steps: List[Dict] = dataclasses.field(default_factory=list)
    traced_steps: List[Dict] = dataclasses.field(default_factory=list)
    requests: List[Dict] = dataclasses.field(default_factory=list)
    first_steps: List[int] = dataclasses.field(default_factory=list)
    reduced: Optional[trace.Reduced] = None
    config: Dict = dataclasses.field(default_factory=dict)
    cell: Dict = dataclasses.field(default_factory=dict)
    device_kind: str = ""


class CompileCounter:
    """Counts lowerings and backend compilations while ``armed``."""

    def __init__(self):
        import jax
        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self.armed and event in _COMPILE_EVENTS:
            self.count += 1


class Slice:
    """The traced slice of a window: its last ``length_s`` seconds. The
    profiler starts on a step boundary inside the window and is stopped
    after it, so that writing the trace costs the window nothing."""

    def __init__(self, ctx: Context, window_start: float, length_s: float):
        self.on = ctx.trace
        self.dir = os.path.join(ctx.scratch, f"trace-{ctx.cell_name}")
        self.begin_at = window_start + max(0.0, ctx.seconds - length_s)
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None

    def tick(self, now: float) -> None:
        if self.on and self.t0 is None and now >= self.begin_at:
            shutil.rmtree(self.dir, ignore_errors=True)
            trace.start(self.dir)
            self.t0 = time.perf_counter()

    def finish(self) -> None:
        if self.t0 is not None and self.t1 is None:
            self.t1 = time.perf_counter()
            trace.stop()

    def covers(self, t_begin: float, t_end: float) -> bool:
        return (self.t0 is not None and self.t1 is not None
                and t_begin >= self.t0 and t_end <= self.t1)

    def reduce(self, top_span: str) -> Optional[trace.Reduced]:
        """Reads the slice back and deletes the trace files."""
        if self.t1 is None:
            return None
        path = trace.find_xplane(self.dir)
        if path is None:
            return None
        reduced = trace.reduce(trace.read_xplane(path), top_span)
        shutil.rmtree(self.dir, ignore_errors=True)
        return reduced


def sized(section: Dict, rehearse: bool) -> Dict:
    """A cell's or a configuration's values, with the ``rehearse`` group laid
    over them for a CPU rehearsal (one level deep)."""
    out = {k: v for k, v in section.items() if k != "rehearse"}
    if rehearse:
        for key, val in section.get("rehearse", {}).items():
            out[key] = ({**out.get(key, {}), **val}
                        if isinstance(val, dict) else val)
    return out


def model_sizes(config: Dict) -> Dict:
    """The published keys of a configuration file, with the rehearsal's
    ``model`` group (tiny widths, CPU only) laid over them if it is there."""
    return {**{k: v for k, v in config.items()
               if not isinstance(v, dict)}, **config.get("model", {})}


def build_model(sizes: Dict, seed: int, train_options: Optional[Dict] = None):
    """``LlamaForCausalLM`` at the configuration's sizes, holding weights
    made by ``reference.make_weights`` from the seed. Returns (model,
    LlamaConfig, weights); the weights are the arrays the model holds."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    if sizes.get("sliding_window") is not None:
        raise ValueError("LlamaForCausalLM has no sliding window")
    options = train_options or {}
    cfg = LlamaConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        intermediate_size=sizes["intermediate_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        max_position_embeddings=sizes["max_position_embeddings"],
        rms_norm_eps=sizes["rms_norm_eps"], rope_theta=sizes["rope_theta"],
        tie_word_embeddings=sizes["tie_word_embeddings"],
        use_flash_attention=options.get("use_flash_attention", True),
        recompute=options.get("recompute", False),
        dtype=sizes["torch_dtype"])
    paddle.seed(seed % (2 ** 31 - 1))
    model = LlamaForCausalLM(cfg)
    weights = reference.make_weights(sizes, seed, jnp.dtype(cfg.dtype))
    named = dict(model.named_parameters())
    if set(named) != set(weights):
        raise RuntimeError(
            f"the model's parameters are not the reference's: "
            f"{sorted(set(named) ^ set(weights))}")
    for name, p in named.items():
        if tuple(p._data.shape) != tuple(weights[name].shape):
            raise RuntimeError(f"{name}: {p._data.shape} in the model, "
                               f"{weights[name].shape} in the reference")
        p._set_data(weights[name])
    return model, cfg, weights


def quantile(values: List[float], q: float) -> float:
    """Linear-interpolated quantile, q in [0, 1]."""
    if not values:
        raise ValueError("quantile of nothing")
    s = sorted(values)
    x = q * (len(s) - 1)
    lo = int(x)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (x - lo)
