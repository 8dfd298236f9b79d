"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the two hot paths once, through the entry points a user
calls, at the full width of the models the repository supports:

* trainer — BERT-Large (24L / hidden 1024 / 16 heads / intermediate 4096 /
  seq 512, 8 samples per chip, bf16 compute, Adam) through ``FFConfig`` ->
  ``FFModel`` -> ``build_bert`` -> ``compile()`` -> ``fit()``: on one chip on
  one, on a four-chip host data-parallel over all four;
* server — GPT-2 small (12L / hidden 768 / 12 heads / vocab 50257, bf16)
  through ``ServingEngine(ff, n_slots=8, max_decode_len=256).generate`` on the
  default path (paged KV, prefix cache on, sync loop, fast decode).

Weights are random, made from the config's seed. Before the two phases each
Pallas kernel's numbers, and the dropless routed expert layer's against a
float32 dense loop at the program's own routing, are checked on the chip
once, and after each phase the
compiled program's text must hold the kernel's Mosaic custom call — a run that
routed to the einsum / gather path fails.

The script refuses to run without a TPU whose ``device_kind`` is in the peak
table: it never selects a smaller size or another backend. Any exception in
any phase is a non-zero exit; the last line of standard output is the result
JSON and is printed only when every phase passed. Lines tagged ``[info]`` are
information for CHANGES.md (compile seconds, step and token times), not
metrics.
"""
from __future__ import annotations

import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

TRAIN_STEPS = 8          # first fit(): step 0 compiles
TRAIN_STEPS_AGAIN = 2    # second fit() on the same model (donated buffers)
# Adam's first steps move every one of 335M weights by the learning rate: at
# 1e-4 (the seed round's rate) one step saturates the 2-class softmax and the
# loss sits at the clip (0.75 -> 17.3 on the CPU, 1.15 -> 6.9 on the chip) —
# the recipe, not the device. At 1e-6 the loss falls step by step.
TRAIN_LR = 1e-6
MAX_NEW_TOKENS = 24

# Kernel tolerances, as the largest |difference| over the largest |reference|
# value. bf16 keeps 8 mantissa bits, so one unit in the last place at the top
# of the range is 2**-8 of the largest value.
#
# flash_attention vs mha_core, bf16 in and out: both round the probabilities
# to bf16 before P.V and the result to bf16, and they accumulate the f32 sums
# in a different tile order — a few last-place units.
FLASH_FWD_TOL = 2.0 ** -6
# ... and the backward adds the bf16 roundings of dS, P and dO on both sides.
FLASH_BWD_TOL = 2.0 ** -5
# flash_decode vs its masked-gather reference at f32 ("highest") matmul
# precision: both compute in f32 from the same stored bf16 / dequantised int8
# rows, so only the summation order and the final rounding to bf16 differ.
FLASH_DECODE_TOL = 2.0 ** -7
# The dropless routed expert layer (ops/moe_ops.py) vs a float32 dense loop
# over the held experts AT THE PROGRAM'S OWN ROUTING, as the relative L2
# error of the output and of every gradient. Set between two readings on the
# v5e (PERF.md section 6, PR 35): bf16 compute reads 0.0029 to 0.0036, and
# the same layer with the grouped products' operands and cotangents in three
# mantissa bits (the benchmark's control d) 0.0815 to 0.0836: the limit is
# four times the one and a fifth of the other.
ROUTED_TOL = 1.5e-2


# One selective state-space mixer (ops/ssm.py) at AI21-Jamba2-3B's widths vs
# the plain float32 reference: the relative L2 error of the recurrent STATE
# the node hands on, after a padded prefill's last real token and after 64
# decode steps from it (SSM_TOL), and of the node's output over those rows
# (SSM_OUT_TOL). The state is where the precision of the scan shows: the
# bf16 projections' rounding of each token's input averages out over the
# state's memory, a rounding of the state itself adds up over it. Set between
# two readings on the v5e (PERF.md section 6, PR 44): the sound program's, and
# the control's — the reference itself with its state rounded to bf16 every
# step, which is what a program that kept ``S`` in bf16 would read at best.
# Readings (my chip run, PR 44; after the prefill, after the decode steps):
# sound 0.00289, 0.00232; control 0.00534, 0.00536; the output 0.00393,
# 0.00317. The sound state's error is the bf16 projections' (x, dt, B), which
# is why the control reads less than twice it.
SSM_TOL = 4.0e-3
SSM_OUT_TOL = 2.0e-2
# One gated delta-rule mixer (ops/gated_delta.py) at Olmo-Hybrid-7B's widths
# vs the plain float32 reference, the same two readings: the recurrent STATE
# after a padded prefill's last real token (the chunked kernel) and after 64
# decode steps from it (the update kernel), and the node's output over those
# rows. Set between the sound program's reading and the control's — the
# reference with its state rounded to bf16 every step (``reduce_precision``).
# Readings (my chip run, PR 46; after the prefill, after the decode steps):
# sound 0.00291, 0.00291; control 0.00806, 0.00757; the output 0.00393,
# 0.00403. The limit is the two readings' geometric mean: 1.6 times the one,
# 0.62 of the other (a matrix state a head takes a bf16 rounding harder than
# the state-space mixer's vector state: the control reads 2.7 times the sound
# program, where SSM_TOL's reads 1.8 times).
GDN_TOL = 4.7e-3
GDN_OUT_TOL = 2.0e-2
# One latent-attention node (ops/latent_attention.py) at GigaChat3.5's widths
# (64 heads, YaRN, neighbour pairs, the gate) vs the plain float32 reference
# at positions past the original context: the relative L2 error of the node's
# OUTPUT over the chunk rows (the absorbed chunk read) and the decode rows
# (the absorbed decode read). Set between the sound program's reading and the
# control's, the reference with ``k_r`` left unrotated (PERF.md section 6,
# PR 53, has both readings).
LATENT_TOL = 2.0e-2


def info(msg: str) -> None:
    print(f"[info] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: {what}")
    print(f"[ok] {what}", flush=True)


def rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def mosaic_calls(compiled_text: str) -> set:
    """Names of the Mosaic (``tpu_custom_call``) kernels in a compiled
    program's text. The hot-path ``pl.pallas_call``s carry a ``name``, which
    XLA keeps as the last scope of the call's ``op_name`` — bare, or inside
    ``jvp(...)`` / ``transpose(jvp(...))`` for a custom_vjp's two halves."""
    names = set()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r"(\w+)\)*/pallas_call", line)
            names.add(m.group(1) if m else "<unnamed>")
    return names


# ------------------------------------------------------------- environment
def check_environment() -> dict:
    """Fail before building anything unless JAX runs on a known TPU."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — jax.devices()[0].platform is "
                 f"{dev.platform!r}; this check runs on the chip only")
    sys.path.insert(0, ROOT)
    from flexflow_tpu import native
    from flexflow_tpu.obs.telemetry import detect_peak_flops
    from flexflow_tpu.utils.compile_cache import ensure_compile_cache

    peak = detect_peak_flops()  # a device_kind outside the table raises
    cache_dir = ensure_compile_cache()
    import importlib.metadata as md

    info(f"device platform={dev.platform} kind={dev.device_kind!r} "
         f"count={len(devices)} peak_bf16_flops={peak:.3g}")
    info(f"jax {jax.__version__} jaxlib {md.version('jaxlib')} "
         f"libtpu {md.version('libtpu')}")
    info(f"compile cache dir: {cache_dir} "
         f"({'from JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'checkout default'})")
    info(f"native runtime core: {native.implementation()}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ------------------------------------------------------- kernel numerics
def check_flash_attention() -> None:
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels.flash_attention import flash_attention
    from flexflow_tpu.ops.attention import _flash_blocks, mha_core

    shape = (8, 16, 512, 64)
    kq, kk, kv, kd = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(r, shape, jnp.bfloat16)
                   for r in (kq, kk, kv, kd))
    bq, bk = _flash_blocks(shape[2], shape[2])

    def through(core):
        def loss(q, k, v):
            out = core(q, k, v)
            return jnp.sum(out.astype(jnp.float32)
                           * do.astype(jnp.float32)), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    flash = through(lambda q, k, v: flash_attention(q, k, v, False, bq, bk))
    ref = through(lambda q, k, v: mha_core(q, k, v))
    text = flash.lower(q, k, v).compile().as_text()
    check({"flash_attention_fwd", "flash_attention_bwd_fused"}
          <= mosaic_calls(text),
          f"flash_attention {shape} blocks ({bq},{bk}) compiles to Mosaic "
          f"kernels {sorted(mosaic_calls(text))}")
    (_, out_f), grads_f = flash(q, k, v)
    (_, out_r), grads_r = ref(q, k, v)
    e = rel_err(out_f, out_r)
    check(e <= FLASH_FWD_TOL,
          f"flash_attention forward vs mha_core: {e:.2e} <= "
          f"{FLASH_FWD_TOL:.2e}")
    for name, gf, gr in zip(("dq", "dk", "dv"), grads_f, grads_r):
        e = rel_err(gf, gr)
        check(e <= FLASH_BWD_TOL,
              f"flash_attention backward {name} vs mha_core: {e:.2e} <= "
              f"{FLASH_BWD_TOL:.2e}")


def check_flash_decode() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.kernels.flash_decode import (_reference_decode,
                                                   flash_decode_pool)
    from flexflow_tpu.serving.kvcache import new_kv_pool, scatter_prefill_kv

    slots, heads, hd, extent = 10, 12, 64, 512
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (slots, heads, hd), jnp.bfloat16)
    # lengths from no key (a free slot: exact zeros back) over the edges
    # of a block and of the kernel's 256-key tile to a slot's full extent
    n_keys = jnp.asarray([0, 1, 15, 16, 17, 100, 255, 256, 257, 512],
                         jnp.int32)
    live = np.asarray(n_keys) > 0
    scale = 1.0 / np.sqrt(hd)
    ref = _reference_decode()
    # the reader's gate asks for whole lanes and 8-row sublanes, not for
    # the dtype's whole tile: an int8 pool at the default block 16 and a
    # bf16 pool at block 8 read through the kernel too
    for label, bs in (("native", 16), ("int8", 16), ("native", 8),
                      ("int8", 32)):
        mb = extent // bs
        n_blocks = slots * mb + 1
        # the pool as the slot writer builds it: one contiguous K and V
        # scattered over every block, K | V side by side on 128 lanes
        flat = tuple(jax.random.normal(k, (1, heads, n_blocks * bs, hd),
                                       jnp.bfloat16) for k in (kk, kv))
        every_block = jnp.arange(n_blocks, dtype=jnp.int32)
        # every slot its own shuffled run of blocks
        tables = jnp.asarray(1 + np.random.default_rng(1).permutation(
            slots * mb).reshape(slots, mb), jnp.int32)
        entry = scatter_prefill_kv(new_kv_pool(flat, n_blocks, bs, label),
                                   flat, every_block, bs)
        pool, scales = entry if label == "int8" else (entry, None)
        fn = jax.jit(lambda q, p, t, n, sc=scales: flash_decode_pool(
            q, p, t, n, sm_scale=scale, scales=sc))
        text = fn.lower(q, pool, tables, n_keys).compile().as_text()
        check("flash_decode" in mosaic_calls(text),
              f"flash_decode ({label} pool, {heads} heads x {2 * hd} "
              f"lanes, block {bs}) compiles to a Mosaic kernel")
        out = fn(q, pool, tables, n_keys)
        check(bool(jnp.all(out[~live] == 0)) and bool(jnp.all(
            jnp.isfinite(out))),
              f"flash_decode ({label} pool, block {bs}): a slot of no keys "
              f"is exact zeros, every value finite")
        with jax.default_matmul_precision("highest"):
            want = ref(q, pool, tables, n_keys, scale, scales)
        e = rel_err(out[live], want[live])
        check(e <= FLASH_DECODE_TOL,
              f"flash_decode ({label} pool, block {bs}) vs masked-gather "
              f"reference: {e:.2e} <= {FLASH_DECODE_TOL:.2e}")


def check_flash_decode_latent() -> None:
    """The kernel's latent read at the ``openpangu-ultra-docqa-8k`` cell's
    row (128 heads against ONE 576-number row a key, padded to 640 lanes,
    the value its first 512) and its chunk read (4 positions a grid step,
    one shared table row), against a float32 gather."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.kernels.flash_decode import flash_decode_pool

    slots, heads, width, v_lanes, bs, extent = 10, 128, 576, 512, 16, 512
    mb = extent // bs
    kq, kp = jax.random.split(jax.random.PRNGKey(3))
    q = jax.random.normal(kq, (slots, heads, width), jnp.bfloat16)
    pool = jnp.pad(jax.random.normal(
        kp, (slots * mb + 1, 1, bs, width), jnp.bfloat16),
        ((0, 0), (0, 0), (0, 0), (0, 64)))
    tables = jnp.asarray(1 + np.random.default_rng(1).permutation(
        slots * mb).reshape(slots, mb), jnp.int32)
    n_keys = jnp.asarray([0, 1, 15, 16, 17, 100, 255, 256, 257, 512],
                         jnp.int32)
    scale = 1.0 / np.sqrt(192.0)

    def gather(q, table_rows, seen):
        """q (n, heads, width) f32, each row of q its own table row and
        count of keys seen."""
        ext = pool[table_rows][:, :, 0].reshape(
            len(table_rows), extent, -1).astype(jnp.float32)
        s = jnp.einsum("nhw,nkw->nhk", q, ext[..., :width]) * scale
        s = jnp.where(jnp.arange(extent)[None, None] < seen[:, None, None],
                      s, -1e30)
        return jnp.einsum("nhk,nkv->nhv", jax.nn.softmax(s, axis=-1),
                          ext[..., :v_lanes])

    fn = jax.jit(lambda q, p, t, n: flash_decode_pool(
        q, p, t, n, sm_scale=scale, v_lanes=v_lanes))
    check("flash_decode" in mosaic_calls(
        fn.lower(q, pool, tables, n_keys).compile().as_text()),
        "flash_decode (latent pool, 128 heads a 640-lane row) compiles to "
        "a Mosaic kernel")
    out = fn(q, pool, tables, n_keys)
    live = np.asarray(n_keys) > 0
    check(bool(jnp.all(out[~live] == 0)) and bool(jnp.all(
        jnp.isfinite(out))), "flash_decode (latent): a slot of no keys is "
        "exact zeros, every value finite")
    with jax.default_matmul_precision("highest"):
        want = gather(q.astype(jnp.float32), tables, n_keys)
    e = rel_err(out[live], want[live])
    check(e <= FLASH_DECODE_TOL, f"flash_decode (latent) vs float32 gather: "
          f"{e:.2e} <= {FLASH_DECODE_TOL:.2e}")
    # the chunk read: 64 positions from 131 on, the last 13 of them pad
    c, t, start, n_new = 64, 4, 131, 51
    qc = jax.random.normal(kq, (c, heads, width), jnp.bfloat16)
    first = jnp.arange(c // t, dtype=jnp.int32) * t
    nk = jnp.where(first < n_new, start + first + 1, 0)
    fn = jax.jit(lambda q, p, t_, n: flash_decode_pool(
        q.reshape(c // t, t * heads, width), p, t_, n, sm_scale=scale,
        v_lanes=v_lanes, tokens=t).reshape(c, heads, v_lanes))
    check("latent_chunk_attention" in mosaic_calls(
        fn.lower(qc, pool, tables[:1], nk).compile().as_text()),
        "the chunk read compiles to the Mosaic kernel latent_chunk_attention")
    out = fn(qc, pool, tables[:1], nk)
    with jax.default_matmul_precision("highest"):
        want = gather(qc.astype(jnp.float32),
                      jnp.broadcast_to(tables[:1], (c, mb)),
                      start + jnp.arange(c) + 1)
    e = rel_err(out[:n_new], want[:n_new])
    check(e <= FLASH_DECODE_TOL and bool(jnp.all(jnp.isfinite(out))),
          f"latent_chunk_attention vs float32 gather over the live rows: "
          f"{e:.2e} <= {FLASH_DECODE_TOL:.2e}")


def check_kv_write() -> None:
    """The pool's in-place write against the scatter it replaces, on the
    chip — where alone the aliased call's hazard exists (it fetches the
    next grid step's block before this one's is written back): a chunk
    whose rows fill three blocks, one with pad rows, and the decode
    step's one row a slot with free slots meeting in the garbage block.
    A move: the stored values are compared bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.serving.kvcache import GARBAGE_BLOCK, write_kv_rows

    for heads, lanes in ((12, 128), (1, 640)):   # K | V heads; a latent row
        _check_kv_write(heads, lanes)


def _check_kv_write(heads: int, lanes: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.serving.kvcache import GARBAGE_BLOCK, write_kv_rows

    bs, n_blocks = 16, 40
    kp, kr = jax.random.split(jax.random.PRNGKey(2))
    pool = jax.random.normal(kp, (n_blocks, heads, bs, lanes), jnp.bfloat16)
    table = np.asarray([7, 3, 31, 12, 25, 9], np.int32)

    def chunk(chunk_len, start, n_new):
        pos = start + np.arange(chunk_len)
        bi = np.where(np.arange(chunk_len) < n_new,
                      table[np.clip(pos // bs, 0, len(table) - 1)],
                      GARBAGE_BLOCK)
        return bi, pos % bs, True

    cases = {
        "token, free slots in the garbage block": (
            np.asarray([5, 6, 8, 0, 0, 0, 11, 0]),
            np.asarray([0, bs - 1, 3, 0, 0, 0, bs - 1, 0]), False),
        "chunk over three blocks": chunk(2 * bs, bs - 5, 2 * bs),
        "chunk with pad rows": chunk(4 * bs, 2 * bs, 2 * bs + 3),
        "chunk inside one block": chunk(bs // 2, 3, bs // 2),
    }
    live = np.arange(n_blocks) != GARBAGE_BLOCK
    for label, (bi, off, consecutive) in cases.items():
        label = f"{heads} x {lanes} lanes, {label}"
        rows = jax.random.normal(kr, (len(bi), heads, lanes), jnp.bfloat16)
        bi, off = jnp.asarray(bi, jnp.int32), jnp.asarray(off, jnp.int32)
        fn = jax.jit(lambda p, r, b, o, c=consecutive: write_kv_rows(
            p, r, b, o, consecutive=c), donate_argnums=(0,))
        text = fn.lower(pool, rows, bi, off).compile().as_text()
        check("kv_write" in mosaic_calls(text),
              f"kv_write ({label}) compiles to a Mosaic kernel")
        want = np.asarray(pool.at[bi, :, off].set(rows))
        got = np.asarray(fn(jnp.copy(pool), rows, bi, off))
        check(np.array_equal(got[live].view(np.uint16),
                             want[live].view(np.uint16)),
              f"kv_write ({label}) equals the scatter bit for bit outside "
              "the garbage block")
        check(bool(np.isfinite(got[GARBAGE_BLOCK].astype(np.float32)).all()),
              f"kv_write ({label}) leaves the garbage block finite")


def check_routed_layer(tokens: int = 8192, d: int = 2048,
                       inter: int = 1024, skewed: bool = False) -> None:
    """Router -> sort-by-expert dispatch -> grouped products -> combine, at
    the widths of the benchmark's ``trinity-mini`` cell (8,192 tokens of
    2,048; 128 experts, top-8, experts 0-15 held at width 1,024; bf16),
    forward and every gradient, against the plain formula in float32 at the
    routing the program chose. A benchmark run cannot hold the routed
    weights' gradients to its reference — top-8 of 128 flips between bf16
    and float32 for a few per cent of tokens and the train driver hands its
    reference no routing (PERF.md section 7) — so they are held here, where
    the choice is the program's on both sides. (The sizes are arguments so
    that the check can be rehearsed off the chip at a tiny size; ``main``
    runs the cell's.) At a seeded router the held pairs fit the layer's row
    bound and the three nodes take their bounded path; ``skewed`` hands the
    selection a bias no score can outweigh on eight held experts, so every
    pair lands here and the whole-buffer fallback is what is held."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.ops import moe_ops
    from flexflow_tpu.ops.base import OpContext

    n, k, held, scale = 128, 8, (0, 16), 2.826
    ids = {"num_experts": n, "held": held}
    bf16 = DataType.DT_BFLOAT16
    router_op = moe_ops.MoERouterOp(
        "r", dict(ids, k=k, route_scale=scale), bf16)
    dispatch_op = moe_ops.MoEDispatchOp("d", ids, bf16, 2)
    experts_op = moe_ops.MoERoutedExpertsOp(
        "e", dict(ids, intermediate=inter), bf16, 2)
    combine_op = moe_ops.MoECombineOp("c", ids, bf16, 4)

    keys = jax.random.split(jax.random.PRNGKey(3), 7)

    def normal(key, shape, std):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(
            jnp.bfloat16)

    x = normal(keys[0], (1, tokens, d), 1.0)       # as an RMS norm leaves it
    dy = normal(keys[1], (1, tokens, d), 1.0)
    router = {"kernel": normal(keys[2], (d, n), (2.0 / (d + n)) ** 0.5),
              "expert_bias": jax.random.uniform(
                  keys[3], (n,), jnp.float32, -0.01, 0.01)}
    if skewed:
        router["expert_bias"] = router["expert_bias"].at[
            held[0]:held[0] + k].add(10.0)
    std = (2.0 / (d + inter)) ** 0.5
    experts = {"gate": normal(keys[4], (held[1], d, inter), std),
               "up": normal(keys[5], (held[1], d, inter), std),
               "down": normal(keys[6], (held[1], inter, d), std)}

    def system(x, kernel, experts):
        ctx = OpContext(stats_out={})
        weights, chosen = router_op.forward(
            {"kernel": kernel, "expert_bias": router["expert_bias"]}, [x],
            ctx)
        rows, sizes, order = dispatch_op.forward({}, [x, chosen], ctx)
        (out,) = experts_op.forward(experts, [rows, sizes], ctx)
        (y,) = combine_op.forward({}, [out, order, weights, chosen], ctx)
        return jnp.sum(y.astype(jnp.float32) * dy.astype(jnp.float32)), \
            (y, chosen, ctx.stats_out["d"])

    def plain(x, kernel, experts, chosen):
        scores = jax.nn.sigmoid(x @ kernel)
        w = jnp.take_along_axis(scores, chosen, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale

        @jax.checkpoint
        def share(m, gate, up, down):
            w_e = jnp.sum(jnp.where(chosen == held[0] + m, w, 0.0), axis=-1)
            return w_e[..., None] * (
                (jax.nn.silu(x @ gate) * (x @ up)) @ down)

        y, _ = jax.lax.scan(
            lambda y, e: (y + share(*e), None), jnp.zeros_like(x),
            (jnp.arange(held[1]), experts["gate"], experts["up"],
             experts["down"]))
        return jnp.sum(y * dy.astype(jnp.float32)), y

    (_, (y, chosen, stats)), grads = jax.jit(jax.value_and_grad(
        system, argnums=(0, 1, 2), has_aux=True))(x, router["kernel"],
                                                  experts)
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                 (x, router["kernel"], experts))
    with jax.default_matmul_precision("highest"):
        (_, y_ref), grads_ref = jax.jit(jax.value_and_grad(
            plain, argnums=(0, 1, 2), has_aux=True))(*f32, chosen)

    sizes = np.asarray(stats["tokens_per_expert"])
    here = int(np.isin(np.asarray(chosen), np.arange(*held)).sum())
    check(int(stats["pairs_here"]) == int(sizes.sum()) == here
          and int(stats["dropped"]) == 0,
          f"routed layer: {here} of {tokens * k} (token, expert) pairs are "
          f"held here, every one reached its group ({sizes.min()}-"
          f"{sizes.max()} rows an expert), none dropped")
    bound = moe_ops._row_bound(tokens * k, ids)
    path = "bounded path" if int(stats["bounded"]) else "whole buffer"
    check(int(stats["bounded"]) == (bound is not None and here <= bound)
          and (here == tokens * k) == skewed,
          f"routed layer: {here} pairs against a bound of {bound} rows took "
          f"the {path}")

    def rel_l2(got, ref):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))

    named = [("output", y, y_ref), ("d input", grads[0], grads_ref[0]),
             ("d router kernel", grads[1], grads_ref[1])] + [
        (f"d experts {w}", grads[2][w], grads_ref[2][w])
        for w in ("gate", "up", "down")]
    errs = {name: rel_l2(got, ref) for name, got, ref in named}
    info("routed layer vs float32 at the program's routing, relative L2: "
         + ", ".join(f"{name} {e:.4f}" for name, e in errs.items()))
    worst = max(errs, key=errs.get)
    check(errs[worst] <= ROUTED_TOL,
          f"routed layer: output and every gradient within {ROUTED_TOL} of "
          f"the float32 dense loop (worst: {worst} {errs[worst]:.4f})")


def check_routed_layer_decode(rows: int = 64, d: int = 7680,
                              inter: int = 2048, lead: float = 4.0,
                              limit: float = 0.0) -> None:
    """The same four nodes at the DECODE shapes of the benchmark's
    ``openpangu-ultra-docqa-8k`` cell (64 rows of 7,680, one a slot; 256
    experts, top-8 of the sigmoid scores alone scaled 2.5, experts 0-15
    held at width 2,048; bf16), forward, against the float32 dense loop at
    the routing the program chose. The cell's own comparison sees a fault
    of the held experts only where it moves a served token off the
    reference's best, and a three-mantissa-bit rounding of the grouped
    products' operands moved none (PERF.md section 6, PR 37: control c
    reads 0): the same rounding is planted here and must read over the
    limit, the sound products under it. ``lead`` scales the held experts'
    router columns: at 4 they draw more pairs than the layer's row bound
    (128 of the 512) and the whole-buffer fallback is what is held; at 1
    the routing is the cell's kind (32 pairs expected) and both the sound
    and the planted products run on the bounded path. ``limit``: the
    experts' SwiGLU clamped there (the ``gigachat35-reasoning-2k`` cell's
    form), with the experts' first two matrices scaled so that it bites."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.ops import moe_ops
    from flexflow_tpu.ops.base import OpContext

    n, k, held, scale = 256, 8, (0, 16), 2.5
    ids = {"num_experts": n, "held": held}
    bf16 = DataType.DT_BFLOAT16
    router_op = moe_ops.MoERouterOp(
        "r", dict(ids, k=k, route_scale=scale, selection_bias=False), bf16)
    dispatch_op = moe_ops.MoEDispatchOp("d", ids, bf16, 2)
    experts_op = moe_ops.MoERoutedExpertsOp(
        "e", dict(ids, intermediate=inter,
                  **({"limit": limit} if limit else {})), bf16, 2)
    combine_op = moe_ops.MoECombineOp("c", ids, bf16, 4)
    keys = jax.random.split(jax.random.PRNGKey(5), 5)

    def normal(key, shape, std):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(
            jnp.bfloat16)

    x = normal(keys[0], (rows, 1, d), 1.0)         # as an RMS norm leaves it
    # a router that sends the held experts rows: their columns lead
    kernel = normal(keys[1], (d, n), (2.0 / (d + n)) ** 0.5)
    kernel = kernel.at[:, :held[1]].multiply(lead)
    std = (2.0 / (d + inter)) ** 0.5
    # under a limit: gate and up products of std 8 and 12 against 10
    experts = {"gate": normal(keys[2], (held[1], d, inter),
                              std * (8.0 if limit else 1.0)),
               "up": normal(keys[3], (held[1], d, inter),
                            std * (12.0 if limit else 1.0)),
               "down": normal(keys[4], (held[1], inter, d), std)}

    def system(x, experts, product):
        ctx = OpContext(stats_out={})
        weights, chosen = router_op.forward({"kernel": kernel}, [x], ctx)
        rows_, sizes, order = dispatch_op.forward({}, [x, chosen], ctx)
        plain = jax.lax.ragged_dot
        jax.lax.ragged_dot = product
        try:
            (out,) = experts_op.forward(experts, [rows_, sizes], ctx)
        finally:
            jax.lax.ragged_dot = plain
        (y,) = combine_op.forward({}, [out, order, weights, chosen], ctx)
        return y, weights, chosen, ctx.stats_out["d"]

    def round3(a):
        m, e = jnp.frexp(a.astype(jnp.float32))
        return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e).astype(a.dtype)

    ragged = jax.lax.ragged_dot
    y, w, chosen, stats = jax.jit(
        lambda x, e: system(x, e, ragged))(x, experts)
    y3 = jax.jit(lambda x, e: system(
        x, e, lambda lhs, rhs, g, **kw: ragged(round3(lhs), round3(rhs), g,
                                               **kw))[0])(x, experts)
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    bites = []
    with jax.default_matmul_precision("highest"):
        y_ref = jnp.zeros_like(xf)
        for m in range(held[1]):
            w_e = jnp.sum(jnp.where(chosen == held[0] + m, wf, 0.0), axis=-1)
            g, u, dn = (experts[name][m].astype(jnp.float32)
                        for name in ("gate", "up", "down"))
            gx, ux = xf @ g, xf @ u
            if limit:
                bites.append(jnp.mean((gx > limit) | (jnp.abs(ux) > limit)))
                gx, ux = jnp.minimum(gx, limit), jnp.clip(ux, -limit, limit)
            y_ref = y_ref + w_e[..., None] * ((jax.nn.silu(gx) * ux) @ dn)
    here = int(np.isin(np.asarray(chosen), np.arange(*held)).sum())
    live = int((np.asarray(stats["tokens_per_expert"]) > 0).sum())
    bound = moe_ops._row_bound(rows * k, ids)
    path = "bounded path" if int(stats["bounded"]) else "whole buffer"
    check(int(stats["pairs_here"]) == here > 0
          and (here > rows) == (lead > 1)
          and int(stats["dropped"]) == 0
          and int(stats["bounded"]) == (here <= bound),
          f"routed layer at decode shapes: {here} of {rows * k} (row, "
          f"expert) pairs are held here, {live} of {held[1]} held experts "
          f"got a row, none dropped; against a bound of {bound} rows they "
          f"took the {path}")
    if limit:
        share = float(np.mean([float(b) for b in bites]))
        check(share > 0.2, f"routed layer at decode shapes: the clamp at "
              f"{limit:g} bites on {share:.0%} of the gate / up products")

    def rel_l2(got):
        got = np.asarray(got, np.float32)
        return float(np.linalg.norm(got - np.asarray(y_ref))
                     / np.linalg.norm(np.asarray(y_ref)))

    sound, planted = rel_l2(y), rel_l2(y3)
    info(f"routed layer at decode shapes vs float32 at the program's "
         f"routing, relative L2: sound {sound:.4f}, grouped products in "
         f"three mantissa bits {planted:.4f} (limit {ROUTED_TOL})")
    check(sound <= ROUTED_TOL < planted,
          f"routed layer at decode shapes: within {ROUTED_TOL} of the "
          f"float32 dense loop ({sound:.4f}), and the grouped products in "
          f"three mantissa bits are not ({planted:.4f})")


def _load_reference(reference_file: str):
    """A plain reference of ``benchmark/reference/`` by its file's name."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "smoke_reference", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "benchmark",
            "reference", reference_file))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return ref


def _check_mixer_layer(what: str, op, reference_file: str, mixer,
                       config: dict, d: int, bucket: int, length: int,
                       steps: int, kernels: dict, key: int, tol: float,
                       out_tol: float, state_of=lambda s: s) -> None:
    """One recurrent mixer node ``op`` at a serving cell's widths, bf16: a
    ``bucket``-row padded prefill of a ``length``-token prompt (the state
    handed on is the one after the last REAL token) and ``steps`` decode
    steps from that state, against the plain reference's whole-sequence
    mixer (``reference_file``'s function ``mixer``) in float32 over prompt +
    steps tokens (``mixer``: its name there, called as the olmo-hybrid and
    jamba references take it, or a callable ``(ref, u, params, config,
    fault)`` -> (output, last state)). The serving cells' own comparison
    sees a precision fault
    only where it moves a served token (PERF.md section 7); this one reads
    the node's output and the state it hands on. The control — the reference
    with its state rounded to bf16 every step — must read over the limit.
    ``kernels``: the Mosaic calls the compiled ``prefill`` / ``decode``
    programs must hold. ``state_of``: the state as the slot rests it -> the
    shape the reference hands back."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.base import OpContext
    from flexflow_tpu.serving.kvcache import ServingState

    ref = _load_reference(reference_file)
    keys = jax.random.split(jax.random.PRNGKey(key), 32)
    params = {name: init(keys[i], shape, jnp.bfloat16)
              for i, (name, (shape, _dt, init)) in enumerate(
                  sorted(op.weight_specs([(1, bucket, d)]).items()))}
    total = length + steps
    u = jax.random.normal(keys[-1], (1, total, d), jnp.float32
                          ).astype(jnp.bfloat16)

    def reference(fault, n):
        def run(u, params):
            with jax.default_matmul_precision("highest"):
                if callable(mixer):
                    return mixer(ref, u[0, :n].astype(jnp.float32), params,
                                 config, fault)
                return getattr(ref, mixer)(
                    u[0, :n].astype(jnp.float32), ref.f32(params), config,
                    fault, with_state=True)

        return [np.asarray(a) for a in jax.jit(run)(u, params)]

    (_, want_mid), (want, want_end) = (reference(None, n)
                                       for n in (length, total))
    (_, ctl_mid), (_, ctl_end) = (reference("bf16_state", n)
                                  for n in (length, total))

    @jax.jit
    def prefill(params, u):
        padded = jnp.pad(u[:, :length], ((0, 0), (0, bucket - length),
                                         (0, 0)))
        sv = ServingState(mode="prefill", max_len=bucket,
                          positions=jnp.zeros((1,), jnp.int32),
                          lengths=jnp.asarray([length], jnp.int32))
        out = op.forward(params, [padded],
                         OpContext(training=False, serving=sv))[0]
        return out[0, :length], sv.cache_out[op.name]

    @jax.jit
    def decode(params, u_t, cache):
        sv = ServingState(mode="decode", max_len=bucket,
                          positions=jnp.zeros((1,), jnp.int32),
                          cache_in={op.name: cache},
                          block_tables=jnp.ones((1, 1), jnp.int32))
        out = op.forward(params, [u_t],
                         OpContext(training=False, serving=sv))[0]
        return out[0, 0], sv.cache_out[op.name]

    rows, cache = prefill(params, u)
    programs = {"prefill": prefill.lower(params, u),
                "decode": decode.lower(params, u[:, :1], cache)}
    for program, kernel in kernels.items():
        calls = mosaic_calls(programs[program].compile().as_text())
        check(kernel in calls, f"{what}: the {program} runs the {kernel} "
                               f"Mosaic kernel ({sorted(calls)})")
    got_mid = np.asarray(state_of(cache[1]), np.float32)[0]
    got = [np.asarray(rows, np.float32)]
    for t in range(length, total):
        row, cache = decode(params, u[:, t:t + 1], cache)
        got.append(np.asarray(row, np.float32)[None])
    got = np.concatenate(got)
    got_end = np.asarray(state_of(cache[1]), np.float32)[0]

    def l2(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    sound = (l2(got_mid, want_mid), l2(got_end, want_end))
    planted = (l2(ctl_mid, want_mid), l2(ctl_end, want_end))
    out = (l2(got[:length], want[:length]), l2(got[length:], want[length:]))
    widths = ", ".join(f"{k} {v}" for k, v in op.attrs.items()
                       if isinstance(v, int) and not isinstance(v, bool))
    info(f"{what} at d {d}, {widths}; "
         f"{length} of {bucket} prefill rows, {steps} decode steps: relative "
         f"L2 error of the state (after the prefill, after the decode "
         f"steps): sound {sound[0]:.5f}, {sound[1]:.5f}; the reference with "
         f"a bf16 state {planted[0]:.5f}, {planted[1]:.5f} (limit "
         f"{tol}); of the output (prefill rows, decode rows) "
         f"{out[0]:.5f}, {out[1]:.5f} (limit {out_tol})")
    check(max(sound) <= tol < min(planted),
          f"{what}: the state handed on is within {tol} of the "
          f"float32 reference's, and the bf16-state control is over it")
    check(max(out) <= out_tol,
          f"{what}: the output is within {out_tol} of the float32 "
          f"reference's")


def check_ssm_layer(d: int = 2560, inner: int = 5120, state: int = 16,
                    conv: int = 4, rank: int = 160, bucket: int = 2048,
                    length: int = 1900, steps: int = 64) -> None:
    """One selective state-space mixer at the ``jamba2-3b-reasoning`` cell's
    widths: the ``selective_scan`` kernel's prefill and the fused one-token
    update (``_check_mixer_layer``)."""
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.base import op_class_for

    eps = 1e-6
    op = op_class_for(OperatorType.OP_SSM_MIXER)(
        "l0_ssm", {"inner_dim": inner, "state_dim": state,
                   "conv_width": conv, "dt_rank": rank, "conv_bias": True,
                   "proj_bias": False, "norm_eps": eps},
        DataType.DT_BFLOAT16)
    _check_mixer_layer(
        "ssm layer", op, "ai21-jamba2-3b.py", "mamba",
        {"mamba_d_state": state, "mamba_d_conv": conv,
         "mamba_dt_rank": rank, "rms_norm_eps": eps},
        d, bucket, length, steps, {"prefill": "selective_scan"}, 44,
        SSM_TOL, SSM_OUT_TOL)


def check_delta_layer(d: int = 3840, heads: int = 30, dk: int = 96,
                      dv: int = 192, conv: int = 4, bucket: int = 1024,
                      length: int = 900, steps: int = 64,
                      key_heads: int = 0) -> None:
    """One gated delta-rule mixer at the ``olmo-hybrid-7b-assist`` cell's
    widths: the ``gated_delta_rule`` kernel's prefill and the
    ``gated_delta_update`` kernel's decode steps (``_check_mixer_layer``);
    the state rests two heads a row and is read through ``unpack_state``.
    ``key_heads``: the grouped form of the ``gigachat35-reasoning-2k``
    cell (``check_delta_layer_grouped``), against that cell's reference."""
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.kernels.gated_delta_rule import unpack_state
    from flexflow_tpu.ops.base import op_class_for

    eps = 1e-6
    attrs = {"num_heads": heads, "key_dim": dk, "value_dim": dv,
             "conv_width": conv, "neg_eigval": not key_heads,
             "norm_eps": eps}
    config = {"linear_num_key_heads": key_heads or heads,
              "linear_key_head_dim": dk, "linear_value_head_dim": dv,
              "linear_conv_kernel_dim": conv, "rms_norm_eps": eps}
    if key_heads:
        attrs.update(num_key_heads=key_heads, gate="sigmoid2_zero_centered")
        config.update(linear_num_value_heads=heads,
                      linear_attn_o_norm_eps=eps, linear_sigmoid_gate_scale=2)

        def mixer(ref, u, params, config, fault):
            return ref.delta_mixer(
                u, _no_alternatives(), params, dict(config, fault=fault),
                u.shape[0], with_state=True)

        what, reference, key = "grouped delta layer", \
            "gigachat35-432b-a28b.py", 53
    else:
        config["linear_allow_neg_eigval"] = True
        what, reference, mixer, key = "delta layer", "olmo-hybrid-7b.py", \
            "delta_mixer", 46
    op = op_class_for(OperatorType.OP_GATED_DELTA_MIXER)(
        "l0_gdn", attrs, DataType.DT_BFLOAT16)
    _check_mixer_layer(
        what, op, reference, mixer, config, d, bucket, length, steps,
        {"prefill": "gated_delta_rule", "decode": "gated_delta_update"}, key,
        GDN_TOL, GDN_OUT_TOL, state_of=lambda s: unpack_state(s, dv))


def _no_alternatives():
    """The gigachat reference's ``pos_a`` with no routing alternative."""
    import jax.numpy as jnp

    return jnp.zeros((0,), jnp.int32)


def check_delta_layer_grouped() -> None:
    """The ``gigachat35-reasoning-2k`` cell's delta-rule mixer: 32 key heads
    under 64 value heads of (128, 128) — a head a row of the state at rest,
    the path PR 49 left for 128 lanes — the sigmoid2 gate over the
    zero-centred norm, a 2,048-row bucket."""
    check_delta_layer(d=7168, heads=64, dk=128, dv=128, bucket=2048,
                      length=1900, key_heads=32)


def check_latent_layer(d: int = 7168, heads: int = 64, context: int = 36864,
                       chunk: int = 1024, steps: int = 16,
                       compare_from: int = 32768) -> None:
    """One latent-attention node at the ``gigachat35-reasoning-2k`` cell's
    widths (64 heads, q 1,536, a 512 + 64 row, YaRN factor 8 over 32,768,
    neighbour pairs, the sigmoid gate), bf16, at positions PAST the original
    context: ``context`` tokens chunk-prefilled into a latent pool (the
    absorbed chunk read, ``latent_chunk_attention``), then ``steps`` decode
    steps (``flash_decode``'s latent read at a 64-head row), against the
    plain reference's materialised float32 attention over the whole
    sequence. Compared: the rows at positions 32,768 and on. The control —
    the reference with ``k_r`` left unrotated — must read over the limit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.base import OpContext, op_class_for
    from flexflow_tpu.serving.kvcache import ServingState, new_kv_pool

    ref = _load_reference("gigachat35-432b-a28b.py")
    yarn = {"type": "yarn", "factor": 8, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 32768}
    config = {"num_attention_heads": heads, "q_lora_rank": 1536,
              "kv_lora_rank": 512, "qk_nope_head_dim": 128,
              "qk_rope_head_dim": 64, "v_head_dim": 128,
              "rope_theta": 100000.0, "rope_scaling": yarn,
              "rope_interleave": True, "gated_attention": True,
              "use_mla_scaling_factor": True, "rms_norm_eps": 1e-6}
    op = op_class_for(OperatorType.OP_LATENT_ATTENTION)(
        "l4_mla", {"embed_dim": d, "num_heads": heads, "q_rank": 1536,
                   "kv_rank": 512, "nope_dim": 128, "rope_dim": 64,
                   "v_dim": 128, "rope_theta": 100000.0, "eps": 1e-6,
                   "causal": True, "rope_scaling": yarn,
                   "rope_interleave": True, "gated": True},
        DataType.DT_BFLOAT16)
    keys = jax.random.split(jax.random.PRNGKey(53), 16)
    params = {name: init(keys[i], shape, jnp.bfloat16)
              for i, (name, (shape, _dt, init)) in enumerate(
                  sorted(op.weight_specs([(1, chunk, d)]).items()))}
    total, bs = context + steps, 16
    u = jax.random.normal(keys[-1], (1, total, d), jnp.float32
                          ).astype(jnp.bfloat16)
    mb = -(-total // bs)
    table = jnp.arange(1, mb + 1, dtype=jnp.int32)
    pool = new_kv_pool(
        (jnp.zeros((1, 1, bs, op.row_width), jnp.bfloat16),), mb + 1, bs,
        "native")

    @jax.jit
    def chunk_step(params, x, pool, start):
        sv = ServingState(mode="chunk", max_len=total, block_size=bs,
                          positions=start[None],
                          lengths=jnp.asarray([chunk], jnp.int32),
                          cache_in={op.name: pool},
                          block_tables=table[None])
        out = op.forward(params, [x], OpContext(training=False,
                                                serving=sv))[0]
        return out[0], sv.cache_out[op.name]

    @jax.jit
    def decode(params, x, pool, pos):
        sv = ServingState(mode="decode", max_len=total, block_size=bs,
                          positions=pos[None], cache_in={op.name: pool},
                          block_tables=table[None])
        out = op.forward(params, [x], OpContext(training=False,
                                                serving=sv))[0]
        return out[0, 0], sv.cache_out[op.name]

    first = compare_from // chunk
    calls = mosaic_calls(chunk_step.lower(
        params, u[:, :chunk], pool, jnp.int32(0)).compile().as_text()) \
        | mosaic_calls(decode.lower(
            params, u[:, :1], pool, jnp.int32(0)).compile().as_text())
    check({"latent_chunk_attention", "flash_decode", "kv_write"} <= calls,
          f"latent layer at {heads} heads: the chunk and decode steps run "
          f"the Mosaic kernels ({sorted(calls)})")
    got = []
    for c in range(context // chunk):
        rows, pool = chunk_step(params, u[:, c * chunk:(c + 1) * chunk],
                                pool, jnp.int32(c * chunk))
        if c >= first:
            got.append(np.asarray(rows, np.float32))
    for t in range(context, total):
        r, pool = decode(params, u[:, t:t + 1], pool, jnp.int32(t))
        got.append(np.asarray(r, np.float32)[None])
    got = np.concatenate(got)

    def reference(fault):
        def run(u, params):
            with jax.default_matmul_precision("highest"):
                return ref.attention(
                    u[0].astype(jnp.float32), _no_alternatives(), params,
                    dict(config, fault=fault), total)[first * chunk:]

        return np.asarray(jax.jit(run)(u, params))

    want, control = reference(None), reference("k_r_unrotated")

    def l2(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    parts = {"chunk rows": slice(0, context - first * chunk),
             "decode rows": slice(context - first * chunk, None)}
    sound = {k: l2(got[v], want[v]) for k, v in parts.items()}
    planted = {k: l2(control[v], want[v]) for k, v in parts.items()}
    info(f"latent layer at d {d}, {heads} heads, positions "
         f"{first * chunk}-{total - 1} (YaRN past 32,768, neighbour pairs, "
         f"gated): relative L2 error of the output: sound "
         f"{sound['chunk rows']:.5f} (chunk rows), "
         f"{sound['decode rows']:.5f} (decode rows); the reference with k_r "
         f"unrotated {planted['chunk rows']:.5f}, "
         f"{planted['decode rows']:.5f} (limit {LATENT_TOL})")
    check(max(sound.values()) <= LATENT_TOL < min(planted.values()),
          f"latent layer: the output past the original context is within "
          f"{LATENT_TOL} of the float32 reference's, and the unrotated-k_r "
          f"control is over it")


# ------------------------------------------------------------------ trainer
def build_trainer(cfg, argv):
    """FFConfig -> FFModel -> build_bert -> compile(), bf16 compute, Adam."""
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
    from flexflow_tpu.models.bert import build_bert

    config = FFConfig()
    config.parse_args(["-b", str(cfg.batch_size), "--compute-dtype", "bf16"]
                      + argv)
    ff = FFModel(config)
    build_bert(ff, cfg)
    ff.compile(optimizer=AdamOptimizer(ff, alpha=TRAIN_LR),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def synthetic_batch(cfg):
    import numpy as np

    rng = np.random.default_rng(0)
    x1 = rng.normal(size=(cfg.batch_size, cfg.seq_len, cfg.hidden)
                    ).astype(np.float32)
    y1 = rng.integers(0, cfg.num_classes, size=(cfg.batch_size,)
                      ).astype(np.int32)
    return x1, y1


def fit_repeated(ff, cfg, batch, steps: int):
    """fit() ``steps`` steps on one repeated batch; per-step (losses,
    walls) from fit()'s telemetry."""
    import numpy as np

    x1, y1 = batch
    ff.fit(np.tile(x1, (steps, 1, 1)), np.tile(y1, steps),
           batch_size=cfg.batch_size, epochs=1, shuffle=False)
    tel = ff.get_telemetry()
    return list(tel.loss_history), list(tel.step_wall_s)


def device_batch(ff, batch):
    """The batch as the train step takes it: sharded like fit() shards it."""
    import jax

    x1, y1 = batch
    return ([jax.device_put(x1, ff.executor.batch_sharding(3))],
            jax.device_put(y1[:, None], ff.executor.batch_sharding(2)))


def train_step_text(ff, batch) -> str:
    """Compiled text of the train step fit() ran."""
    import jax

    xd, yd = device_batch(ff, batch)
    return ff.executor.make_train_step().lower(
        ff.params, ff.opt_state, xd, yd, jax.random.PRNGKey(0)
    ).compile().as_text()


def on_distinct_devices(arrays) -> int:
    """Fewest distinct devices any of ``arrays`` has shards on."""
    return min(len({s.device for s in a.addressable_shards})
               for a in arrays)


def train_phase(cfg, n_chips: int, steps: int, steps_again: int):
    """compile() + fit() twice on one repeated synthetic batch. Returns
    (losses, compile seconds, compiled train-step text)."""
    import jax
    import numpy as np

    t0 = time.perf_counter()
    ff = build_trainer(cfg, ["--only-data-parallel"] if n_chips > 1 else [])
    build_s = time.perf_counter() - t0
    check(int(ff.mesh.devices.size) == n_chips,
          f"trainer mesh {dict(ff.mesh.shape)} spans all {n_chips} chip(s)")

    batch = synthetic_batch(cfg)
    losses, walls = [], []
    for n in (steps, steps_again):
        # fit() a second time on the same model: the step donates params
        # and optimizer state, so a stale reference would be a deleted array
        more_losses, more_walls = fit_repeated(ff, cfg, batch, n)
        losses += more_losses
        walls += more_walls
    if n_chips > 1:
        check(on_distinct_devices(jax.tree_util.tree_leaves(ff.params)
                                  + device_batch(ff, batch)[0]) == n_chips,
              f"every parameter and the batch have shards on {n_chips} "
              f"distinct devices")
    info(f"trainer: build+init {build_s:.1f} s, first step (compile) "
         f"{walls[0]:.1f} s, steady step "
         f"{1e3 * float(np.median(walls[2:steps])):.1f} ms "
         f"(median of steps 2..{steps - 1}, each synced for the loss)")
    return losses, walls[0], train_step_text(ff, batch)


# ------------------------------------------------------------------- server
def serve_phase(cfg, max_new_tokens: int):
    """ServingEngine.generate twice on one engine; the second wave repeats
    the first wave's prompts, so it runs on prefix-cache hits. Returns
    (first-wave streams, engine, first-wave wall seconds)."""
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.models.gpt2 import build_gpt2
    from flexflow_tpu.serving import ServingEngine

    config = FFConfig()
    # one replica sits on one chip, also on a four-chip host (every replica
    # of a ServingFleet shares its model's mesh; four one-chip replicas are
    # ROADMAP R2)
    config.parse_args(["-b", str(cfg.batch_size), "--compute-dtype", "bf16",
                       "--only-data-parallel", "--mesh-shape", "1"])
    ff = FFModel(config)
    build_gpt2(ff, cfg)
    ff.compile(loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    check(int(ff.mesh.devices.size) == 1, "server replica sits on one chip")
    eng = ServingEngine(ff, n_slots=8, max_decode_len=256)
    check((eng.kv_cache, eng.serve_loop, eng.exact_decode,
           eng._prefix is not None) == ("paged", "sync", False, True),
          "server runs the default path: paged KV, prefix cache on, sync "
          "loop, fast decode")

    rng = np.random.default_rng(0)
    lengths = [5, 12, 17, 30, 33, 48, 64, 70, 90, 100, 120]
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in lengths]
    prompts.append(list(prompts[4]))  # the same prompt twice

    t0 = time.perf_counter()
    first = eng.generate(prompts, max_new_tokens=max_new_tokens)
    cold_s = time.perf_counter() - t0
    _check_wave(eng, cfg, prompts, first, max_new_tokens, "first wave")
    check(first[4] == first[-1],
          "the two equal prompts give equal greedy streams")
    p50 = eng.stats.p50_token_ms()

    t0 = time.perf_counter()
    again = eng.generate(prompts, max_new_tokens=max_new_tokens)
    warm_s = time.perf_counter() - t0
    _check_wave(eng, cfg, prompts, again, max_new_tokens, "second wave")
    check(eng.stats.prefix_hits > 0,
          f"second generate() on the same engine reuses cached prefixes "
          f"({eng.stats.prefix_hits} hits, "
          f"{eng.stats.prefix_tokens_reused} prompt tokens reused)")
    agree = float(np.mean([a == b for a, b in zip(first, again)]))
    info(f"server: first generate() {cold_s:.1f} s (compiles every prefill "
         f"bucket and the decode step), second {warm_s:.2f} s; p50 token "
         f"{p50:.2f} ms first wave, {eng.stats.p50_token_ms():.2f} ms "
         f"second; {eng.stats.tokens_generated} tokens/wave; "
         f"{agree:.0%} of streams identical across the cold and "
         f"prefix-hit waves (bf16 fast decode promises no bitwise match)")
    return first, eng, cold_s


def _check_wave(eng, cfg, prompts, streams, max_new_tokens, label) -> None:
    check(eng.stats.outcomes == {"ok": len(prompts)},
          f"{label}: every request leaves with outcome ok "
          f"({eng.stats.outcomes})")
    check(all(len(s) == max_new_tokens for s in streams),
          f"{label}: every stream has {max_new_tokens} tokens")
    check(all(0 <= t < cfg.vocab_size for s in streams for t in s),
          f"{label}: every token is inside the vocabulary")
    check(eng.decode_compiles == 1,
          f"{label}: decode_compiles == 1 (got {eng.decode_compiles})")


def decode_step_text(eng) -> str:
    """Compiled text of the decode step the engine just served with."""
    import jax.numpy as jnp

    fn = eng._decode_fn(guard=eng._last_guard)
    tokens = jnp.zeros((eng.n_slots, 1), jnp.int32)
    return fn.lower(eng.model.params, [tokens], eng.state
                    ).compile().as_text()


# --------------------------------------------------------------------- main
def main() -> None:
    t_start = time.perf_counter()
    device = check_environment()
    import numpy as np

    if sys.argv[1:]:  # the named checks alone: python chip_smoke.py <name>..
        for name in sys.argv[1:]:
            globals()[name]()
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return

    from flexflow_tpu.models.bert import BertConfig
    from flexflow_tpu.models.gpt2 import GPT2Config
    from flexflow_tpu.obs import enable as obs_enable

    obs_enable()  # per-step losses and walls come from fit()'s telemetry

    check_flash_attention()
    check_flash_decode()
    check_flash_decode_latent()
    check_kv_write()
    check_routed_layer()
    check_routed_layer(skewed=True)
    check_routed_layer_decode()
    check_routed_layer_decode(lead=1.0)
    check_routed_layer_decode(rows=128, d=7168, lead=1.0, limit=10.0)
    check_ssm_layer()
    check_delta_layer()
    check_delta_layer_grouped()
    check_latent_layer()

    n_chips = device["count"]
    bert = BertConfig(batch_size=8 * n_chips, seq_len=512, hidden=1024,
                      num_heads=16, num_layers=24, intermediate=4096)
    losses, train_compile_s, text = train_phase(
        bert, n_chips, TRAIN_STEPS, TRAIN_STEPS_AGAIN)
    check(bool(np.all(np.isfinite(losses))),
          f"trainer: loss finite at all {len(losses)} steps "
          f"({', '.join(f'{v:.4f}' for v in losses)})")
    check(losses[TRAIN_STEPS - 1] < losses[0],
          f"trainer: loss fell over the first fit() "
          f"({losses[0]:.4f} -> {losses[TRAIN_STEPS - 1]:.4f})")
    check({"flash_attention_fwd", "flash_attention_bwd_fused"}
          <= mosaic_calls(text),
          f"train step runs the flash_attention Mosaic kernels, forward "
          f"and backward ({sorted(mosaic_calls(text))})")

    gpt2 = GPT2Config(batch_size=8, seq_len=256, hidden=768, num_heads=12,
                      num_layers=12, intermediate=3072, vocab_size=50257)
    _streams, eng, serve_cold_s = serve_phase(gpt2, MAX_NEW_TOKENS)
    calls = mosaic_calls(decode_step_text(eng))
    check({"flash_decode", "kv_write"} <= calls,
          f"decode step runs the flash_decode and kv_write Mosaic kernels "
          f"({sorted(calls)})")

    info(f"compile seconds: trainer first step {train_compile_s:.1f}, "
         f"server first generate() {serve_cold_s:.1f}; total wall "
         f"{time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
