"""Kernel selection layer.

Mirrors the role of PHI's per-backend kernel registry (SURVEY §2.1): ops with
both an XLA composition and a hand-written Pallas kernel pick at call time.
Default policy: Pallas on real TPU devices, XLA composition elsewhere
(Pallas-on-CPU runs in interpret mode — correct but slow, used by tests).
The platform is ``jax.default_backend()``; a kernel module that fails to
import is an error, never a silent switch to the composition.
"""
from __future__ import annotations

import os

import jax

__all__ = [
    "use_pallas", "use_pallas_explicit", "set_use_pallas", "attention_impl",
    "interpret_mode", "layer_norm_impl",
    "rmsnorm_impl", "softmax_ce_impl", "paged_decode_impl",
]

_FORCE = os.environ.get("PADDLE_TPU_USE_PALLAS")  # "1" | "0" | None
_override = None


def set_use_pallas(flag: bool | None):
    global _override
    _override = flag


def interpret_mode() -> bool:
    """Pallas kernels compile through Mosaic on a TPU and run in the Pallas
    interpreter everywhere else."""
    return jax.default_backend() != "tpu"


def _explicit_choice():
    """The user's explicit Pallas on/off choice, or None when unset:
    set_use_pallas override > PADDLE_TPU_USE_PALLAS env > FLAGS_use_pallas."""
    if _override is not None:
        return _override
    if _FORCE is not None:
        return _FORCE == "1"
    from ..framework.flags import flag_value

    fv = flag_value("FLAGS_use_pallas")
    if fv != "" and fv is not None:
        return str(fv).lower() in ("1", "true")
    return None


def use_pallas_explicit() -> bool:
    """True only when the user EXPLICITLY forced Pallas on — never from the
    platform default. For ops where the measured chip numbers show the XLA
    composition matching or beating the kernel (e.g. the RNNT lattice), the
    kernel stays available but opt-in."""
    choice = _explicit_choice()
    return bool(choice)


def use_pallas() -> bool:
    choice = _explicit_choice()
    if choice is not None:
        return choice
    return not interpret_mode()


def attention_impl():
    from ..nn.functional.attention import sdpa_ref

    if use_pallas():
        from .flash_attention import flash_attention_pallas

        return flash_attention_pallas
    return sdpa_ref


def rmsnorm_impl():
    """Fused RMSNorm(+residual) kernel — OPT-IN (use_pallas_explicit) and
    never timed on the chip: the XLA composition is the default until a run
    of ``mistral7b-train-2k`` says otherwise (ROADMAP Design 6)."""
    if use_pallas_explicit():
        from .rmsnorm import rmsnorm_residual_pallas

        return rmsnorm_residual_pallas
    return None


def softmax_ce_impl():
    """Streaming softmax-CE kernel — OPT-IN and never timed on the chip,
    like rmsnorm_impl (ROADMAP Design 6)."""
    if use_pallas_explicit():
        from .softmax_ce import softmax_ce_pallas

        return softmax_ce_pallas
    return None


def x64_off():
    """Context manager disabling x64 weak-type promotion while tracing a
    Pallas kernel (x64 python-literal promotion trips Mosaic's index
    lowering)."""
    return jax.enable_x64(False)


def paged_decode_impl():
    """Selector for the serving engine's decode op of one layer (write the
    new K/V rows into the pool, ragged paged attention over it; mirrors
    attention_impl): the Pallas kernel, in place, when the policy picks
    Pallas, else the jnp mirror — the mirror is also the path taken on CPU
    test runs, where it is authoritative for semantics."""
    from .paged_attention import paged_decode_pallas, paged_decode_ref

    if use_pallas():
        return paged_decode_pallas
    return paged_decode_ref


def layer_norm_impl():
    """Selector for the fused-layernorm path (mirrors attention_impl):
    returns the Pallas kernel when the policy picks Pallas, else None
    (caller uses its jnp composition)."""
    if use_pallas():
        from .layernorm import layer_norm_pallas

        return layer_norm_pallas
    return None
