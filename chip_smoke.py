"""Chip smoke: the Llama trainer and the serving engine, end to end, on a TPU.

    python chip_smoke.py

drives the two main paths once through the entry points a user takes, at
Llama-2-7B's published widths (hidden 4096, 32 heads x 128, intermediate
11008, vocabulary 32000; depth is the only thing cut) with random weights
made from a seed:

- train: ``python -m paddle_tpu.distributed.launch --backend tpu`` starts one
  worker that builds the mesh and ``LlamaPipelineTrainer`` + ``AdamW`` and
  takes five steps (2 layers, batch 4 x 2048, remat off);
- serve: a 4-layer bf16 ``LlamaForCausalLM`` in an ``LLMEngine`` inside a
  ``LocalReplica`` behind ``FleetRouter`` and ``Gateway`` answers six HTTP
  ``POST /v1/completions`` (plain prefill, prefix reuse + tail prefill, a
  decode batch wider than one);
- train4: the same trainer on ``{"dp": 2, "mp": 2}``, three steps, whenever
  four devices are visible (reported as not run otherwise, never simulated);
  its losses must match the one-chip leg's.

Each leg checks what comes out (token counts, finite losses in a band, the
Pallas kernels present as Mosaic custom calls in the compiled step and in
agreement with their jnp references on the chip) and fails rather than fall
back: no TPU, a leg that fails, a kernel replaced by its composition — all
exit non-zero with no result line. On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

This parent process never imports JAX: a chip belongs to one process at a
time, so the legs run one after another, each in a child of its own.
There is no CPU mode; ``tests/test_chip_smoke.py`` rehearses the legs on CPU
at a tiny width with the kernels in interpret mode.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import glob
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
RESULT_MARK = "CHIP_SMOKE_LEG "
TOTAL_BUDGET_S = 1140          # the contract allows 1200 s, compilation included

# Llama-2-7B widths (meta-llama/Llama-2-7b config.json); only depth is cut
LLAMA_7B_WIDTHS = dict(
    vocab_size=32000, hidden_size=4096, intermediate_size=11008,
    num_attention_heads=32, num_key_value_heads=32,
    max_position_embeddings=2048)

# 2 layers = 666,914,816 parameters; f32 weights + Adam moments are 8.0 GB
TRAIN = dict(widths=LLAMA_7B_WIDTHS, layers=2, batch=4, seq=2048, steps=5)
# 4 layers in bf16 = 2.1 GB of weights, 0.5 GB of KV pool; the paged kernel
# alone is also checked at the benchmark's 32 slots (a 2.1 GB pool of two layers)
SERVE = dict(widths=LLAMA_7B_WIDTHS, layers=4, block_size=16, max_slots=4,
             max_model_len=2048, prompt_lens=(40, 300, 1500),
             shared_prefix=1024, tail_len=200, new_tokens=32, paged_slots=32)

FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv")
PAGED_KERNEL = "paged_attention"

# Tolerances of kernel-vs-reference checks on bf16 inputs: the references run
# in float32 at "highest" matmul precision, the kernels multiply in bf16 and
# accumulate in float32, so errors are a few bf16 ulps (2**-8) of the value's
# scale. |kernel - ref| <= ATOL + RTOL * |ref|.
KERNEL_ATOL = 2e-2
KERNEL_RTOL = 2e-2
# one-chip vs four-chip loss: bf16 compute in another reduction order. The
# first chip run differed by 6e-6 relative at the third step.
LOSS_MATCH_RTOL = 1e-3
# first loss of a randomly initialised model: ln(vocab) plus half the
# variance of its logits; the band is ln(vocab) - 0.2 .. ln(vocab) + 0.6
LOSS_BAND = (-0.2, 0.6)


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# inside a leg (these import JAX; the parent never calls them)
# ---------------------------------------------------------------------------

def describe_backend():
    """Platform, device kind, device count and versions, as JAX reports."""
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    print(f"backend {jax.default_backend()} device {info} jax {jax.__version__} "
          f"jaxlib {jaxlib.__version__} libtpu {libtpu} "
          f"python {sys.version.split()[0]}", flush=True)
    return info


def _assert_close(name, got, ref):
    """|got - ref| <= KERNEL_ATOL + KERNEL_RTOL |ref| everywhere, all finite."""
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(np.all(np.isfinite(got)), f"{name}: non-finite values")
    err = np.abs(got - ref)
    ratio = float((err / (KERNEL_ATOL + KERNEL_RTOL * np.abs(ref))).max())
    print(f"  {name}: max abs err {err.max():.3e} ({ratio:.2f} of tolerance)",
          flush=True)
    check(ratio <= 1.0, f"{name}: off its reference by {err.max():.3e} "
                        f"({ratio:.2f}x the tolerance)")


def flash_vs_reference(batch, seq, heads, head_dim):
    """Flash forward and backward against ``sdpa_ref`` on bf16 inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels.flash_attention import flash_attention_pallas
    from paddle_tpu.nn.functional.attention import sdpa_ref

    rng = np.random.RandomState(0)
    shape = (batch, seq, heads, head_dim)
    q, k, v, w = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                  for _ in range(4))

    def loss(impl):
        def f(q, k, v, w):
            out = impl(q, k, v, is_causal=True)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True))

    (_, out), grads = loss(flash_attention_pallas)(q, k, v, w)
    # the reference one batch row at a time: its score matrix is O(S^2)
    ref_fn = loss(sdpa_ref)
    ref_out, ref_grads = [], [[], [], []]
    with jax.default_matmul_precision("highest"):
        for b in range(batch):
            row = [a[b:b + 1].astype(jnp.float32) for a in (q, k, v, w)]
            (_, o), g = ref_fn(*row)
            ref_out.append(np.asarray(o))
            for acc, gi in zip(ref_grads, g):
                acc.append(np.asarray(gi))
    print(f"flash attention vs sdpa_ref at {list(shape)} bf16, causal:",
          flush=True)
    _assert_close("out", out, np.concatenate(ref_out))
    for name, got, ref in zip(("dq", "dk", "dv"), grads, ref_grads):
        _assert_close(name, got, np.concatenate(ref))


def paged_vs_reference(slots, heads, kv_heads, head_dim, block_size,
                       max_blocks):
    """The paged decode kernel (the new rows written in place into the
    second layer of a two-layer pool, donated as the engine donates it)
    against its jnp mirror, ragged contexts."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels.paged_attention import (
        paged_attention_ref, paged_decode_pallas, paged_decode_ref)

    rng = np.random.RandomState(1)
    num_blocks = slots * max_blocks + 1
    q = jnp.asarray(rng.standard_normal((slots, heads, head_dim)),
                    jnp.bfloat16)
    k_new, v_new = jnp.asarray(rng.standard_normal(
        (2, slots, kv_heads, head_dim)), jnp.bfloat16)
    pool = jax.random.normal(
        jax.random.PRNGKey(1),
        (2, num_blocks, 2, kv_heads, block_size, head_dim), jnp.bfloat16)
    # every slot owns a shuffled run of blocks; contexts from one token (an
    # idle slot) to the full table, none a multiple of the block size but
    # the last, with one block and a token, and a few hundred, among them
    tables = (1 + rng.permutation(slots * max_blocks)).reshape(
        slots, max_blocks).astype(np.int32)
    full = max_blocks * block_size
    ctx = np.linspace(1, full, slots).astype(np.int32)
    ctx[1:-1] += 3
    ctx[1:3] = np.minimum((block_size + 1, 300), full)
    _, ref_pool = jax.jit(functools.partial(paged_decode_ref, layer_idx=1))(
        q, k_new, v_new, pool, tables, ctx)
    got, got_pool = jax.jit(
        functools.partial(paged_decode_pallas, layer_idx=1),
        donate_argnums=(3,))(q, k_new, v_new, pool, tables, ctx)
    same_pool = bool((got_pool == ref_pool).all())
    del pool, got_pool
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(paged_attention_ref)(
            q.astype(jnp.float32), ref_pool[1].astype(jnp.float32), tables,
            ctx)
    print(f"paged decode vs its mirror at [{slots} slots, "
          f"{heads} heads, {head_dim}], block {block_size}, contexts "
          f"{ctx.tolist()}, bf16:", flush=True)
    _assert_close("out", got, ref)
    check(same_pool, "the pool after the kernel's write is not the mirror's")


def kernels_in_step(ir_dir, step_name, kernels, mosaic):
    """Check the module JAX compiled for ``jit(step_name)`` holds each named
    Pallas kernel, as a Mosaic custom call when ``mosaic`` (on a TPU)."""
    files = sorted(glob.glob(os.path.join(ir_dir, f"*jit_{step_name}_*")))
    check(files, f"no compiled module named jit_{step_name} was dumped")
    lines = [line for f in files
             for line in open(f, errors="replace").read().splitlines()]
    # a Mosaic kernel is a custom call that carries kernel_name = "<name>";
    # an interpreted one leaves "<name>/pallas_call" in the op locations
    marker = "tpu_custom_call" if mosaic else "pallas_call"
    for name in kernels:
        check(any(name in line and marker in line for line in lines),
              f"jit({step_name}): no {marker} named {name} — the kernel was "
              f"{'interpreted or ' if mosaic else ''}replaced by a composition")
    n_custom = sum("@tpu_custom_call" in line for line in lines)
    print(f"jit({step_name}): kernels {list(kernels)} present as {marker}; "
          f"{n_custom} Mosaic custom call(s) in the module", flush=True)


@contextlib.contextmanager
def _leg_setup():
    """Common set-up of a leg: the compile cache, a dump of every module JAX
    compiles (where the kernels are looked for), fallback warnings as errors.
    Yields (dump dir, a function reporting what the leg has written to the
    compile cache so far)."""
    import warnings

    import jax

    from paddle_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()

    def entries():
        return len(glob.glob(os.path.join(cache_dir, "*")))

    before = entries()
    ir_dir = tempfile.mkdtemp(prefix="chip_smoke_ir_")
    jax.config.update("jax_dump_ir_to", ir_dir)
    try:
        with warnings.catch_warnings():
            # a kernel that steps aside for the O(S^2) composition fails here
            warnings.filterwarnings("error", message="flash attention")
            yield ir_dir, lambda: {"dir": cache_dir, "entries_before": before,
                                   "entries_written": entries() - before}
    finally:
        # back to the flag's own default
        jax.config.update("jax_dump_ir_to", os.getenv("JAX_DUMP_IR_TO", ""))
        shutil.rmtree(ir_dir, ignore_errors=True)


def train_leg(size, degrees, steps, ref_losses=None):
    """Build the mesh and the trainer, take ``steps`` steps, check them."""
    import jax
    import numpy as np

    from paddle_tpu.core import native
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.models import LlamaConfig
    from paddle_tpu.models.llama_pipeline import LlamaPipelineTrainer
    from paddle_tpu.optimizer import AdamW

    on_tpu = jax.default_backend() == "tpu"
    w = size["widths"]
    n_dev = math.prod(degrees.values())
    print("native runtime: " + ("built from csrc/" if native.available()
                                else "not built, pure-Python path"),
          flush=True)
    with _leg_setup() as (ir_dir, cache_report):
        if n_dev == 1:
            # before the trainer fills the chip: the reference is O(S^2)
            flash_vs_reference(
                size["batch"], size["seq"], w["num_attention_heads"],
                w["hidden_size"] // w["num_attention_heads"])

        # remat off keeps the forward kernel's residuals, so the backward
        # pass is the two backward kernels and nothing recomputed
        os.environ["PADDLE_TPU_REMAT_POLICY"] = "off"
        # arrays that are not this trainer's (held, so no id is reused)
        older = {id(a): a for a in jax.live_arrays()}
        mesh = build_mesh(degrees=degrees)
        devices = list(mesh.devices.flat)
        check(len(set(devices)) == n_dev
              and all(d.platform == jax.default_backend() for d in devices),
              f"mesh {degrees} is not {n_dev} distinct "
              f"{jax.default_backend()} devices: {devices}")
        trainer = LlamaPipelineTrainer(
            LlamaConfig(num_hidden_layers=size["layers"], **w), mesh,
            AdamW(learning_rate=1e-4), n_micro=1, zero_stage=1, seed=0)
        rng = np.random.RandomState(1)
        losses = []
        for i in range(steps):  # a fresh batch every step
            x = rng.randint(0, w["vocab_size"], (size["batch"], size["seq"]))
            y = rng.randint(0, w["vocab_size"], (size["batch"], size["seq"]))
            t0 = time.monotonic()
            loss = jax.block_until_ready(trainer.step(x, y))
            if i == 0:
                first_step_s = round(time.monotonic() - t0, 1)
            losses.append(float(np.asarray(loss)))
        n_params = trainer.num_params()
        print(f"train {degrees}: {n_params:,} parameters, {steps} steps of "
              f"{size['batch']} x {size['seq']}; losses "
              f"{[round(l, 4) for l in losses]}; state set-up, compile and "
              f"first step {first_step_s} s", flush=True)

        check(all(math.isfinite(l) for l in losses),
              f"non-finite loss: {losses}")
        ln_v = math.log(w["vocab_size"])
        lo, hi = ln_v + LOSS_BAND[0], ln_v + LOSS_BAND[1]
        check(lo <= losses[0] <= hi,
              f"first loss {losses[0]:.4f} outside [{lo:.2f}, {hi:.2f}] "
              f"around ln(vocab) = {ln_v:.2f}")
        check(all(a != b for a, b in zip(losses, losses[1:])),
              f"loss did not change from step to step: {losses}")
        kernels_in_step(ir_dir, "train_step", FLASH_KERNELS, mosaic=on_tpu)

        # every weight matrix and its Adam moments: hidden x hidden f32 and up
        spread = (_check_spread(mesh, 2 * w["hidden_size"] ** 2, older)
                  if n_dev > 1 else {})
        if ref_losses is not None:
            for i, (got, ref) in enumerate(zip(losses, ref_losses)):
                check(abs(got - ref) <= LOSS_MATCH_RTOL * abs(ref),
                      f"step {i}: loss {got:.5f} on {degrees} vs {ref:.5f} "
                      f"on one chip (tolerance {LOSS_MATCH_RTOL:.0e} relative)")
            print(f"losses match the one-chip leg's {ref_losses} within "
                  f"{LOSS_MATCH_RTOL:.0e} relative", flush=True)
        hbm = devices[0].memory_stats() or {}   # None on a CPU device
        return {"losses": losses, "params": n_params,
                "first_step_s": first_step_s,
                "hbm": {k: hbm.get(k) for k in
                        ("bytes_limit", "peak_bytes_in_use", "bytes_in_use")},
                "compile_cache": cache_report(), **spread}


def _check_spread(mesh, min_bytes, older):
    """Every live array of ``min_bytes`` or more (but for those with an id in
    ``older``) spans the whole mesh with the number of distinct shards its
    spec names, and every device holds its share of the bytes."""
    import jax

    devices = set(mesh.devices.flat)
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    sharded = 0
    for a in jax.live_arrays():
        if a.is_deleted() or a.nbytes < min_bytes or id(a) in older:
            continue
        spec = getattr(a.sharding, "spec", None)
        check(spec is not None and set(a.sharding.device_set) == devices,
              f"array {a.shape} {a.dtype} lives on "
              f"{sorted(d.id for d in a.sharding.device_set)}, not on the mesh")
        named = [ax for part in spec if part is not None
                 for ax in ((part,) if isinstance(part, str) else part)]
        want = math.prod(shape[ax] for ax in named)
        shards = a.addressable_shards
        check({s.device for s in shards} == devices
              and len({str(s.index) for s in shards}) == want
              and all(s.data.shape == a.sharding.shard_shape(a.shape)
                      for s in shards),
              f"array {a.shape} with spec {spec}: shards do not span the "
              f"devices its spec says")
        sharded += want > 1
    check(sharded > 0, "no array is sharded over a mesh axis")
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
              for d in sorted(devices, key=lambda d: d.id)}
    print(f"{sharded} arrays sharded over mesh axes; bytes in use per device "
          f"{in_use}", flush=True)
    known = [b for b in in_use.values() if b is not None]
    if known:  # the CPU rehearsal's devices report no memory statistics
        check(len(known) == len(devices) and min(known) > 0.5 * max(known),
              f"device memory is not spread over the mesh: {in_use}")
    return {"bytes_in_use": in_use, "sharded_arrays": sharded}


def make_prompts(size, vocab):
    """Two waves of three prompts. The second wave's long prompt repeats the
    first's leading ``shared_prefix`` tokens, so it is served by prefix reuse
    and a tail prefill; the rest are plain prefills in three length buckets."""
    import numpy as np

    rng = np.random.RandomState(2)
    short, mid, long_ = size["prompt_lens"]

    def toks(n):
        return rng.randint(1, vocab, n).tolist()

    first = [toks(short), toks(mid), toks(long_)]
    second = [toks(short), toks(mid),
              first[2][:size["shared_prefix"]] + toks(size["tail_len"])]
    return first, second


def serve_leg(size):
    """Gateway -> FleetRouter -> LocalReplica -> LLMEngine over HTTP."""
    import jax

    import paddle_tpu
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (FleetRouter, Gateway, LLMEngine,
                                    LocalReplica)

    on_tpu = jax.default_backend() == "tpu"
    w = size["widths"]

    def factory():
        paddle_tpu.seed(0)
        model = LlamaForCausalLM(
            LlamaConfig(num_hidden_layers=size["layers"], **w))
        model.to(dtype="bfloat16")
        return LLMEngine(model, block_size=size["block_size"],
                         max_slots=size["max_slots"],
                         max_model_len=size["max_model_len"])

    with _leg_setup() as (ir_dir, cache_report):
        paged_vs_reference(
            size["paged_slots"], w["num_attention_heads"],
            w["num_key_value_heads"],
            w["hidden_size"] // w["num_attention_heads"], size["block_size"],
            max_blocks=size["max_model_len"] // size["block_size"])

        t0 = time.monotonic()
        replica = LocalReplica("r0", factory)
        # a step that compiles a new prefill bucket holds the driver thread
        # for tens of seconds: that is not a dead replica
        router = FleetRouter([replica], probe_timeout_s=600,
                             affinity_block_size=size["block_size"])
        gateway = None
        try:
            router.start(wait_healthy_s=600)
            check(replica.state.value == "healthy",
                  f"replica did not come up: state {replica.state.value}")
            gateway = Gateway(router).start()
            engine = replica.engine
            weights = sum(v.nbytes for v in engine.params.values())
            print(f"serving on {gateway.host}:{gateway.port} after "
                  f"{time.monotonic() - t0:.1f} s; weights "
                  f"{weights / 1e9:.2f} GB, KV pool "
                  f"{engine.cache.pool.nbytes / 1e9:.2f} GB", flush=True)
            answers = []
            for wave in make_prompts(size, w["vocab_size"]):
                answers += _post_wave(gateway, wave, size["new_tokens"])
            stats = engine.stats()
        finally:
            if gateway is not None:
                gateway.stop()
            router.close()

        # an HTTP 200 is not the test (a failed prefill fails only its own
        # request and generate() returns partial lists): the counts are
        for prompt, (status, body) in answers:
            check(status == 200, f"HTTP {status}: {body}")
            choice = body["choices"][0]
            toks = choice["token_ids"]
            check(len(toks) == size["new_tokens"]
                  and choice["finish_reason"] == "length",
                  f"prompt of {len(prompt)} tokens: {len(toks)} tokens, "
                  f"finish_reason {choice['finish_reason']!r}")
            check(all(0 <= t < w["vocab_size"] for t in toks),
                  f"token out of the vocabulary: {toks}")
        n = len(answers)
        check(stats["num_finished"] == n and stats["num_failed"] == 0
              and stats["num_cancelled"] == 0
              and stats["num_preemptions"] == 0,
              f"engine counters: {stats['num_finished']} finished, "
              f"{stats['num_failed']} failed, {stats['num_cancelled']} "
              f"cancelled, {stats['num_preemptions']} preempted of {n}")
        traces = stats["prefill_traces"]      # tail prefills have tuple keys
        prefix = stats["prefix_cache"]
        shared_blocks = size["shared_prefix"] // size["block_size"]
        check(any(isinstance(k, tuple) for k in traces)
              and prefix["hits"] >= 1
              and prefix["blocks_saved"] >= shared_blocks,
              f"no prefix reuse: prefill traces {traces}, prefix cache "
              f"{prefix['hits']} hits / {prefix['blocks_saved']} blocks")
        check(sum(not isinstance(k, tuple) for k in traces) >= 3,
              f"fewer than three plain prefill buckets: {traces}")
        print(f"engine: {stats['num_finished']} finished, "
              f"{stats['total_generated_tokens']} tokens, prefill traces "
              f"{sorted(map(str, traces))}, decode traces "
              f"{stats['decode_traces']}, prefix cache {prefix['hits']} "
              f"hit(s) / {prefix['blocks_saved']} blocks saved", flush=True)
        kernels_in_step(ir_dir, "decode", (PAGED_KERNEL,), mosaic=on_tpu)
        return {"requests": n, "tokens": stats["total_generated_tokens"],
                "prefill_traces": sorted(map(str, traces)),
                "prefix_hits": prefix["hits"],
                "compile_cache": cache_report()}


def _post_wave(gateway, prompts, new_tokens):
    """POST the prompts to /v1/completions at once (the decode batch is as
    wide as the wave); returns [(prompt, (status, body))]."""
    import http.client
    import threading

    def post(i):
        conn = http.client.HTTPConnection(gateway.host, gateway.port,
                                          timeout=600)
        try:
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt": prompts[i], "temperature": 0.0,
                                     "max_tokens": new_tokens}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            out[i] = (resp.status, json.loads(resp.read()))
        finally:
            conn.close()

    out = [None] * len(prompts)
    threads = [threading.Thread(target=post, args=(i,))
               for i in range(len(prompts))]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    check(not any(t.is_alive() for t in threads) and None not in out,
          f"requests did not all return: {out}")
    print(f"{len(prompts)} prompts of {[len(p) for p in prompts]} tokens "
          f"answered {time.monotonic() - t0:.1f} s after they were sent "
          f"(compiles included)", flush=True)
    return list(zip(prompts, out))


def run_leg(args):
    """Child entry: one leg on the chip, one result line."""
    import jax

    device = describe_backend()
    check(jax.default_backend() == "tpu" and device["platform"] == "tpu",
          f"the default backend is {jax.default_backend()!r}, not a TPU")
    if args.leg == "train":
        result = train_leg(TRAIN, {"dp": 1}, TRAIN["steps"])
    elif args.leg == "train4":
        check(device["count"] >= 4, f"{device['count']} devices, need four")
        ref = [float(v) for v in args.ref_losses.split(",")]
        result = train_leg(TRAIN, {"dp": 2, "mp": 2}, len(ref), ref_losses=ref)
    else:
        result = serve_leg(SERVE)
    print(RESULT_MARK + json.dumps(
        {"leg": args.leg, "ok": True, "device": device, **result}),
        flush=True)
    return 0


# ---------------------------------------------------------------------------
# the parent: no JAX here
# ---------------------------------------------------------------------------

def _run_child(cmd, timeout_s):
    """Run one child in its own process group; returns (rc, output). The
    whole group is killed on the way out, whatever happened."""
    proc = subprocess.Popen(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
        return proc.returncode, out
    except subprocess.TimeoutExpired:
        return 124, f"timed out after {timeout_s:.0f} s"
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()


def _leg_result(text):
    for line in reversed(text.splitlines()):
        if line.startswith(RESULT_MARK):
            return json.loads(line[len(RESULT_MARK):])
    return None


def _parent_leg(name, leg_args, timeout_s, through_launcher):
    """Start one leg, echo what it printed, return its result or None."""
    print(f"=== leg {name} (limit {timeout_s:.0f} s)", flush=True)
    t0 = time.monotonic()
    script = [os.path.join(ROOT, "chip_smoke.py"), "--leg", name] + leg_args
    log_dir = None
    try:
        if through_launcher:
            # the way a training job starts; the worker's output is its log
            log_dir = tempfile.mkdtemp(prefix="chip_smoke_log_")
            cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
                   "--backend", "tpu", "--nproc_per_node", "1",
                   "--log_dir", log_dir] + script
        else:
            cmd = [sys.executable] + script
        rc, out = _run_child(cmd, timeout_s)
        if log_dir is not None:
            log = os.path.join(log_dir, "workerlog.0")
            if os.path.exists(log):
                out = out + open(log, errors="replace").read()
    finally:
        if log_dir is not None:
            shutil.rmtree(log_dir, ignore_errors=True)
    result = _leg_result(out) if rc == 0 else None
    lines = out.splitlines()
    if result is None:
        lines = lines[-80:]        # the end of the log says why
    for line in lines:
        print(f"  | {line}")
    verdict = "passed" if result else f"FAILED (exit code {rc})"
    print(f"=== leg {name} {verdict} in {time.monotonic() - t0:.0f} s",
          flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--leg", choices=["train", "serve", "train4"],
                    help="(internal) run one leg in this process")
    ap.add_argument("--ref-losses", default="",
                    help="(internal) the one-chip losses train4 must match")
    args = ap.parse_args(argv)
    if args.leg:
        return run_leg(args)

    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print(f"chip_smoke: no paddle_tpu package beside {__file__}: run it "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    deadline = time.monotonic() + TOTAL_BUDGET_S

    def left():
        return max(deadline - time.monotonic(), 1.0)

    failed = []
    train = _parent_leg("train", [], min(420, left()), through_launcher=True)
    if train is None:
        failed.append("train")
    serve = _parent_leg("serve", [], min(420, left()), through_launcher=False)
    if serve is None:
        failed.append("serve")
    devices = [r["device"] for r in (train, serve) if r]
    n_dev = devices[0]["count"] if devices else 0
    if train and n_dev >= 4:
        ref = ",".join(repr(l) for l in train["losses"][:3])
        train4 = _parent_leg("train4", ["--ref-losses", ref],
                             min(300, left()), through_launcher=True)
        if train4 is None:
            failed.append("train4")
        else:
            devices.append(train4["device"])
    else:
        print(f"=== leg train4 not run: {n_dev} device(s) visible, it needs "
              f"four and the one-chip losses", flush=True)
    if not failed and any(d != devices[0] for d in devices):
        failed.append(f"legs disagree on the device: {devices}")
    if failed:
        print(f"chip_smoke: FAILED: {', '.join(failed)}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
