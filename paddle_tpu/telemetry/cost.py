"""Roofline cost model: FLOPs + HBM bytes per compiled trace.

Counts of what a compiled serving trace asks of the chip, with the same
per-op cost discipline GSPMD uses to reason about partitioned programs:

- :func:`jaxpr_cost` walks a (closed) jaxpr and accumulates **FLOPs**
  (``dot_general`` exactly from its dimension numbers — every matmul and
  einsum in the model lowers to it — plus elementwise/reduction ops at one
  flop per output/input element) and **HBM bytes** (the trace's top-level
  inputs + outputs: the minimum traffic a perfectly-fused execution must
  move, which for a memory-bound decode step — weights + KV pool — is the
  roofline-relevant number).
- :func:`estimate_fn_cost` is the entry point the serving engine calls at
  trace time: ``jax.make_jaxpr`` on the exact python callable + arguments
  the engine is about to jit, so the estimate covers precisely the padded
  shapes the compiled trace will execute (bucket padding included).
- :func:`xla_cost_analysis` cross-checks against the backend's own
  ``compiled.cost_analysis()`` where the jax version/backend exposes it
  (it re-traces and compiles, so it is a tool for tests and offline
  analysis, never the serving hot path).
- :func:`register_trace` records the estimate per ``(callable, bucket)``
  in a process-global registry (fingerprinted by model config so identical
  engines share one estimate) and publishes ``trace_flops`` /
  ``trace_bytes`` / ``trace_arithmetic_intensity`` gauges.
- :func:`platform_peaks` + :func:`roofline_time_s` turn an estimate into
  the roofline model's time for it, ``max(flops / peak_flops, bytes /
  peak_bw)``: what ``serving/tenancy.py`` prices a tenant's FLOPs and bytes
  by. No share of a peak is taken here: a time, a rate or a share comes
  from ``benchmark/run.py`` on a chip.
"""
from __future__ import annotations

import numpy as np

from .metrics import registry
from ..analysis import locksan

__all__ = [
    "jaxpr_cost", "estimate_fn_cost", "xla_cost_analysis",
    "register_trace", "lookup", "traces", "clear",
    "platform_peaks", "roofline_time_s",
]

# primitives that move/reshape data but compute nothing (counted as zero
# flops; their traffic is covered by the whole-trace byte accounting)
_ZERO_FLOP = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "convert_element_type",
    "slice", "dynamic_slice", "dynamic_update_slice", "concatenate",
    "gather", "scatter", "squeeze", "expand_dims", "iota", "rev",
    "pad", "copy", "stop_gradient", "split", "bitcast_convert_type",
    "device_put", "constant", "empty", "select_and_scatter_add",
})

# reductions: one flop per *input* element (the sum/max tree)
_REDUCE_PREFIXES = ("reduce_", "cum", "arg")


def _aval_elems(aval) -> int:
    try:
        n = 1
        for s in aval.shape:
            n *= int(s)
        return n
    except Exception:  # lint: allow-silent(cost model is advisory; unknown aval counts as 0)
        return 0


def _aval_bytes(aval) -> int:
    try:
        return _aval_elems(aval) * np.dtype(aval.dtype).itemsize
    except Exception:  # lint: allow-silent(cost model is advisory; unknown dtype counts as 0)
        return 0


def _dot_general_flops(eqn) -> int:
    """2*M*N*K*batch from the dimension numbers — exact for every matmul
    and einsum (they all lower to dot_general)."""
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = 1
    for i in lb:
        batch *= int(lhs[i])
    k = 1
    for i in lc:
        k *= int(lhs[i])
    m = 1
    for i in range(len(lhs)):
        if i not in lc and i not in lb:
            m *= int(lhs[i])
    n = 1
    for i in range(len(rhs)):
        if i not in rc and i not in rb:
            n *= int(rhs[i])
    return 2 * batch * m * n * k


def _sub_jaxprs(value):
    """Yield any Jaxpr/ClosedJaxpr objects hiding in an eqn param (pjit,
    custom_jvp/vjp, remat, scan bodies, ...) — generic recursion so the
    walk survives jax version drift in primitive names."""
    vals = value if isinstance(value, (tuple, list)) else (value,)
    for v in vals:
        jx = getattr(v, "jaxpr", None)
        if jx is not None and hasattr(jx, "eqns"):
            yield jx                     # ClosedJaxpr
        elif hasattr(v, "eqns"):
            yield v                      # raw Jaxpr


def _walk(jaxpr, acc):
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "dot_general":
            f = _dot_general_flops(eqn)
            acc["matmul_flops"] += f
            continue
        inner = False
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                inner = True
                _walk(sub, acc)
        if inner:
            continue
        if prim in _ZERO_FLOP:
            continue
        if prim.startswith(_REDUCE_PREFIXES):
            acc["elementwise_flops"] += sum(
                _aval_elems(v.aval) for v in eqn.invars
                if hasattr(v, "aval"))
            continue
        # default: elementwise — one flop per output element
        acc["elementwise_flops"] += sum(
            _aval_elems(v.aval) for v in eqn.outvars)


def jaxpr_cost(closed_jaxpr) -> dict:
    """FLOPs + HBM bytes of one trace. ``bytes`` counts the top-level
    inputs (weights, KV pool, tokens) plus outputs — the minimum HBM
    traffic of the compiled program, which is the roofline bound for a
    memory-bound step. Arithmetic intensity is flops/byte."""
    jx = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    acc = {"matmul_flops": 0, "elementwise_flops": 0}
    _walk(jx, acc)
    in_bytes = sum(_aval_bytes(v.aval) for v in jx.invars)
    in_bytes += sum(_aval_bytes(v.aval) for v in jx.constvars)
    out_bytes = sum(_aval_bytes(v.aval) for v in jx.outvars)
    flops = acc["matmul_flops"] + acc["elementwise_flops"]
    nbytes = in_bytes + out_bytes
    return {
        "flops": flops,
        "matmul_flops": acc["matmul_flops"],
        "elementwise_flops": acc["elementwise_flops"],
        "bytes": nbytes,
        "input_bytes": in_bytes,
        "output_bytes": out_bytes,
        "arithmetic_intensity": flops / nbytes if nbytes else 0.0,
    }


def estimate_fn_cost(fn, *args, **kwargs) -> dict:
    """Trace ``fn`` abstractly (``jax.make_jaxpr`` — no XLA compile) and
    walk the jaxpr. The caller is responsible for suspending any python
    side effects the traced function carries (the engine's trace
    counters). ``fn`` is traced through a fresh wrapper object so jax's
    tracing cache never aliases this probe with the caller's own
    ``jax.jit(fn)`` — the jit must still see (and python-execute) its own
    first trace."""
    import jax

    def _probe(*a, **k):
        return fn(*a, **k)

    return jaxpr_cost(jax.make_jaxpr(_probe)(*args, **kwargs))


def xla_cost_analysis(fn, *args, **kwargs) -> dict | None:
    """Best-effort ``compiled.cost_analysis()`` cross-check: returns the
    backend's own {flops, bytes accessed, ...} dict, or None when the jax
    version/backend does not expose it. Re-traces AND compiles — offline
    use only."""
    try:
        import jax

        lowered = jax.jit(fn).lower(*args, **kwargs)
        ca = lowered.compile().cost_analysis()
        if isinstance(ca, (list, tuple)):      # older jax: one per device
            ca = ca[0] if ca else None
        return dict(ca) if ca else None
    except Exception:  # lint: allow-silent(xla cost analysis is version-dependent; None = unavailable)
        return None


# ---------------------------------------------------------------------------
# trace-cost registry (per callable+bucket, fingerprinted)
# ---------------------------------------------------------------------------

_LOCK = locksan.Lock("cost.registry")
_TRACES: dict[tuple, dict] = {}     # (callable, bucket) -> entry
_CM = None


def _cost_metrics():
    global _CM
    if _CM is None:
        reg = registry()
        ls = ("callable", "bucket")
        _CM = (
            reg.gauge("trace_flops",
                      "modeled FLOPs of one compiled trace", ls),
            reg.gauge("trace_bytes",
                      "modeled HBM bytes (inputs+outputs) of one compiled "
                      "trace", ls),
            reg.gauge("trace_arithmetic_intensity",
                      "modeled flops/byte of one compiled trace", ls),
        )
    return _CM


def register_trace(name: str, bucket: str, cost: dict, *,
                   fingerprint=None, **meta) -> dict:
    """Record one trace's cost estimate (idempotent per (name, bucket));
    publishes the ``trace_*`` gauges. Returns the stored entry."""
    entry = {"callable": name, "bucket": str(bucket),
             "fingerprint": fingerprint, **cost, **meta}
    with _LOCK:
        _TRACES[(name, str(bucket))] = entry
    fl, by, ai = _cost_metrics()
    fl.labels(callable=name, bucket=str(bucket)).set(cost.get("flops", 0))
    by.labels(callable=name, bucket=str(bucket)).set(cost.get("bytes", 0))
    ai.labels(callable=name, bucket=str(bucket)).set(
        cost.get("arithmetic_intensity", 0.0))
    return entry


def lookup(name: str, bucket: str, fingerprint=None) -> dict | None:
    """A previously-registered estimate — only when the fingerprint (model
    config + engine geometry) matches, so two different models sharing a
    bucket label never share a cost."""
    with _LOCK:
        entry = _TRACES.get((name, str(bucket)))
    if entry is None:
        return None
    if fingerprint is not None and entry.get("fingerprint") != fingerprint:
        return None
    return dict(entry)


def traces() -> list[dict]:
    with _LOCK:
        return [dict(e) for e in _TRACES.values()]


def clear():
    with _LOCK:
        _TRACES.clear()


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

# peak dense flop/s and peak HBM bandwidth bytes/s per chip, keyed by
# ``jax.Device.device_kind``: the package's one table. The CPU entry is a
# placeholder that lets tenancy price requests on dev hosts, not truth.
# ``benchmark/lib/peaks.py`` keeps its own table on purpose: a yardstick
# does not import the constants of what it measures.
_PEAKS = {
    # v5e, Google Cloud "TPU v5e" documentation: 197 bf16 TFLOP/s, 819 GB/s
    "TPU v5 lite": (197e12, 819e9),
    "cpu": (1e11, 2e10),
}


def platform_peaks(device_kind: str | None = None) -> dict:
    """{device_kind, flops_per_s, bytes_per_s} of one device (default: this
    process's first). A device kind the table does not know is an error,
    not a default."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    if device_kind not in _PEAKS:
        raise ValueError(
            f"no peaks recorded for device kind {device_kind!r}; add it to "
            f"telemetry.cost._PEAKS with its source")
    flops, bw = _PEAKS[device_kind]
    return {"device_kind": device_kind, "flops_per_s": flops,
            "bytes_per_s": bw}


def roofline_time_s(cost: dict, peaks: dict | None = None) -> float:
    """The roofline lower bound on wall time: compute-bound or
    memory-bound, whichever dominates."""
    peaks = peaks or platform_peaks()
    return max(cost.get("flops", 0) / peaks["flops_per_s"],
               cost.get("bytes", 0) / peaks["bytes_per_s"])
