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
    "prefill_attention_impl",
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


# The shortest padded prompt whose prefill attention takes the flash kernel;
# shorter ones take the einsum composition, which is faster there (one v5e
# chip, bf16, a layer's attention at a valid length of 3/4 of the bucket,
# einsum vs kernel: 32 heads 0.033 vs 0.034 ms at 128, 0.040 vs 0.040 at
# 256, 0.054 vs 0.072 at 512, 0.175 vs 0.159 at 1024, 1.17 vs 0.44 at
# 2048; 64 heads, window 512: 0.092 vs 0.124 at 512, 0.65 vs 0.31 at 1024).
# The kernel's tiles multiply in float32; a bucket of one block gains
# nothing from skipping blocks.
PREFILL_KERNEL_MIN_LEN = 1024


def prefill_attention_impl(length: int):
    """Selector for the serving engine's attention over a whole prompt of
    ``length`` tokens (batch 1, padded to its bucket; mirrors
    paged_decode_impl): the flash kernel's forward when the policy picks
    Pallas and the prompt is ``PREFILL_KERNEL_MIN_LEN`` or longer, one
    Mosaic call a layer that reads no key block outside the window or past
    the valid length; else ``sdpa_window_ref``, the einsum composition
    (``[H, P, P]`` scores) and the path of CPU test runs. Both take
    ``(q, k, v, *, is_causal, window, valid_len)``."""
    if use_pallas() and length >= PREFILL_KERNEL_MIN_LEN:
        from .flash_attention import flash_attention_prefill

        return flash_attention_prefill
    from ..nn.functional.attention import sdpa_window_ref

    return sdpa_window_ref


def layer_norm_impl():
    """Selector for the fused-layernorm path (mirrors attention_impl):
    returns the Pallas kernel when the policy picks Pallas, else None
    (caller uses its jnp composition)."""
    if use_pallas():
        from .layernorm import layer_norm_pallas

        return layer_norm_pallas
    return None
