"""The latent-attention decoder with sandwich norms and a routed top-k layer
(models/pangu.py) against its plain reference (tests/reference_pangu.py):
float32 on the CPU at tiny widths, so that no routing choice can flip. The
forward pass; the serving path — prefill then decode through the latent pool,
chunked prefill, a prefix hit with its clone — against the reference's FULL
forward under tests/serving_oracle.py's contract; absorbed = materialised; the
shares add up to the uncut layer; one case per piece of the mathematics that
the comparison refuses when the piece is left out; ``flash_decode``'s latent
read in interpret mode at tile edges; the latent pool written in place in all
four serving programs; the GPT-2 XL decode kernel's digest; the benchmark's
blocked reference equal to the plain one; bf16 at rest never a float32 tree.
"""
import hashlib
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_pangu as ref
from serving_oracle import assert_matches_reference

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "benchmark", "tests", "cells", "configs",
                       "pangu-tiny.json")) as _f:
    TINY = json.load(_f)
FIELDS = TINY["builder"]["fields"]
BATCH, SEQ, BLOCK, MAX_LEN = 2, 32, 8, 64
#: forward against reference, relative to the reference's largest logit:
#: float32 both sides, another order of summation. Measured 1e-6 (3.2e-6 on
#: logits of 3.2); the limit is 100 times that, and the mildest leave-one-out
#: case (the 2.5 scale) reads 0.4.
FORWARD_TOL = 1e-4


def pangu_config(**overrides):
    from flexflow_tpu.models.pangu import PanguConfig

    kwargs = {field: TINY[key] for field, key in FIELDS.items()}
    kwargs.update(batch_size=BATCH, seq_len=SEQ)
    kwargs.update(overrides)
    return PanguConfig(**kwargs)


def build(cfg, seed=5, argv=()):
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.models.pangu import build_pangu

    config = FFConfig()
    config.parse_args(["-b", str(cfg.batch_size), *argv])
    config.seed = seed
    ff = FFModel(config)
    build_pangu(ff, cfg)
    ff.compile(loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


@pytest.fixture(scope="module")
def system():
    ff = build(pangu_config())
    params0 = jax.device_get(ff.params)
    # lift the norm gains off 1 so that a gain left out would show
    rng = np.random.default_rng(11)
    for name, group in params0.items():
        for w in group:
            if group[w].ndim == 1:
                group[w] = (1 + 0.1 * rng.standard_normal(
                    group[w].shape)).astype(np.float32)
    ff.params = jax.device_put(params0)
    return ff, params0


def ids(seed=0, n=SEQ):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], size=n).astype(np.int32)


def reference_logits(params0, seq, leave_out=None):
    return np.asarray(ref.logits(params0, seq, TINY, leave_out=leave_out))


@pytest.fixture(scope="module")
def forward(system):
    ff, params0 = system
    x = np.stack([ids(0), ids(1)])
    got = np.asarray(ff.executor.make_forward()(ff.params, [x]))
    return x, got


def test_parameter_count_is_the_builders(system):
    from flexflow_tpu.models.pangu import pangu_param_count

    ff, _ = system
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(ff.params))
    assert held == pangu_param_count(pangu_config())
    assert not any("expert_bias" in g for g in ff.params.values())


def test_forward_matches_the_reference(system, forward):
    _, params0 = system
    x, got = forward
    for b in range(BATCH):
        want = reference_logits(params0, x[b])
        assert np.abs(got[b] - want).max() <= FORWARD_TOL * np.abs(
            want).max()


@pytest.mark.parametrize("piece", ref.LEAVE_OUT)
def test_comparison_refuses_a_piece_left_out(system, forward, piece):
    """rotary on ``k_r``, either latent norm, a post-norm of the sandwich,
    the 2.5 scale, ``norm_topk_prob``: each, dropped from the reference,
    puts the system outside the limit the sound comparison meets."""
    _, params0 = system
    x, got = forward
    want = reference_logits(params0, x[0], leave_out=piece)
    assert np.abs(got[0] - want).max() > 100 * FORWARD_TOL * np.abs(
        want).max()


# ------------------------------------------------------------ serving path
def latent_pool_state(cache, prompt_len):
    from flexflow_tpu.serving.kvcache import (DecodeState, blocks_per_slot,
                                              new_kv_pool,
                                              scatter_prefill_kv)

    mb = blocks_per_slot(MAX_LEN, BLOCK)
    row = jnp.arange(1, mb + 1, dtype=jnp.int32)
    caches = {name: scatter_prefill_kv(
        new_kv_pool(entry, mb + 1, BLOCK, "native"), entry, row, BLOCK)
        for name, entry in cache.items()}
    return DecodeState(caches=caches,
                       lengths=jnp.asarray([prompt_len], jnp.int32),
                       block_tables=row[None])


def test_prefill_then_decode_through_the_latent_pool(system):
    """Materialised prefill rows, then absorbed decode rows read back from
    the pool (one 24-lane row a token, padded to 128), teacher-forced: every
    row within the oracle's tolerance of the reference's FULL forward and
    the same greedy token."""
    ff, params0 = system
    seq = ids(3, 40)
    want = reference_logits(params0, seq)
    n, bucket = 13, 16
    pre = ff.executor.make_prefill_step(bucket_len=bucket,
                                        max_decode_len=MAX_LEN)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = seq[:n]
    logits_p, last, cache = pre(ff.params, [jnp.asarray(padded)],
                                jnp.asarray([n], np.int32))
    (entry,) = next(iter(cache.values()))
    assert entry.shape == (1, 1, MAX_LEN, 24)      # [c_kv | k_r], no heads
    assert_matches_reference(np.asarray(logits_p)[0, :n], want[:n],
                             "prefill rows")
    state = latent_pool_state(cache, n)
    assert next(iter(state.caches.values())).shape[1:] == (1, BLOCK, 128)
    dec = ff.executor.make_decode_step(MAX_LEN, BLOCK)
    rows = []
    for t in range(n, len(seq)):
        lg, state, _counters = dec(ff.params,
                                   [jnp.asarray(seq[None, t:t + 1])], state)
        rows.append(np.asarray(lg)[0])
    assert_matches_reference(np.stack(rows), want[n:], "decode rows")


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_prefill_matches_the_full_forward(system, chunk):
    """A 29-token prompt through the chunk program (chunk x extent scores,
    rotary positions from the chunk's start): the next-token row within the
    oracle's tolerance of the reference's."""
    ff, params0 = system
    prompt = ids(4, 29)
    want = reference_logits(params0, prompt)[-1]
    pre = ff.executor.make_prefill_step(bucket_len=chunk,
                                        max_decode_len=MAX_LEN)
    cache = jax.eval_shape(pre, ff.params, [jnp.zeros((1, chunk), jnp.int32)],
                           jnp.asarray([1], np.int32))[2]
    state = latent_pool_state(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache), 0)
    fn = ff.executor.make_chunk_prefill_step(chunk, MAX_LEN, BLOCK)
    row = state.block_tables[0]
    for start in range(0, len(prompt), chunk):
        n = min(chunk, len(prompt) - start)
        x = np.zeros((1, chunk), np.int32)
        x[0, :n] = prompt[start:start + n]
        last, state, counters = fn(ff.params, [jnp.asarray(x)], state, row,
                                   jnp.int32(start), jnp.int32(n))
        # the routed graph's chunk step hands its routing counters out last
        assert counters.shape == (5,) and int(counters[4]) == 2
    assert_matches_reference(np.asarray(last)[0], want, "chunked prefill")


@pytest.mark.parametrize("chunk", [16, 32])
def test_prefix_hit_with_its_clone_through_the_engine(system, chunk):
    """Two asks of one document through ``ServingEngine`` with chunked
    prefill on: the second maps the first's blocks, clones the shared
    partial block (the document ends mid-block) and prefills its question
    alone; every token either stream chose is the reference's best at its
    position (float32: no near tie at this seed), and the routing counters
    came out of the decode step with the tokens."""
    from flexflow_tpu.serving import ServingEngine

    ff, params0 = system
    eng = ServingEngine(ff, n_slots=4, max_decode_len=MAX_LEN,
                        kv_block_size=BLOCK, buckets=(16, 32, 64),
                        prefill_chunk_tokens=chunk)
    shapes = []
    plain = eng._chunk_fn
    eng._chunk_fn = lambda shape: shapes.append(shape) or plain(shape)
    doc = ids(6, 35)                     # 4 whole blocks and 3 rows
    asks = [np.concatenate([doc, ids(7 + k, 5 + k)]) for k in range(2)]
    outs = [eng.generate([a], max_new_tokens=6)[0] for a in asks]
    assert eng.stats.prefix_hits == 1
    assert eng.stats.prefix_tokens_reused >= 32
    # the question after the cached document (6 + 3 rows of its partial
    # block) takes ONE chunk
    assert shapes == [chunk] * (4 if chunk == 16 else 3)
    for prompt, out in zip(asks, outs):
        seq = np.concatenate([prompt, np.asarray(out, np.int32)])
        rows = reference_logits(params0, seq[:-1])[len(prompt) - 1:]
        chosen = rows[np.arange(len(out)), np.asarray(out)]
        assert np.max(rows.max(axis=1) - chosen) <= 1e-4
    assert eng.stats.moe_pairs_here > 0
    assert 0 < eng.stats.moe_experts_live <= eng.stats.decode_steps * 2 * 8
    assert eng.stats.summary()["moe_load_max_permille"] >= 1000
    # every layer-step is counted, the prefill chunks' apart; at this tiny
    # size a row buffer is one tile, so none is on the bounded path
    assert eng.stats.moe_layer_steps == eng.stats.decode_steps * 2
    assert eng.stats.moe_chunk_layer_steps == eng.stats.chunked_prefills * 2
    assert eng.stats.moe_bounded_steps == 0
    assert eng.stats.moe_chunk_bounded_steps == 0
    # the latent row is priced once a token and layer, not once a head
    assert eng._kv_row_bytes() == 3 * 128 * 4


def test_absorbed_equals_materialised(system):
    """One query row against 19 cached rows, both forms of the node."""
    from flexflow_tpu.ops.latent_attention import LatentAttentionOp

    ff, _ = system
    node = next(n for n in ff.executor.pcg.compute_nodes()
                if isinstance(n.op, LatentAttentionOp))
    op, p = node.op, ff.params[node.name]
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((1, 19, 64)).astype(np.float32))
    pos = jnp.arange(19, dtype=jnp.int32)[None]
    q_n, q_r = op._queries(p, x, pos)
    rows = op._rows(p, x, pos)
    mask = jnp.ones((1, 1, 19), bool)
    mat = op._materialised(p, q_n[:, -1:], q_r[:, -1:], rows, mask)[0]
    # the absorbed form reads the rows back from a pool (off the chip by
    # gather): three blocks of eight behind one table row
    from flexflow_tpu.serving.kvcache import (new_kv_pool, prefill_kv_entry,
                                              scatter_prefill_kv)

    entry = prefill_kv_entry(rows[:, None], None, 24)
    table = jnp.arange(1, 4, dtype=jnp.int32)
    pool = scatter_prefill_kv(new_kv_pool(entry, 4, 8, "native"), entry,
                              table, 8)
    ab = op._absorbed(p, q_n[:, -1:], q_r[:, -1:], pool, table[None],
                      jnp.asarray([[19]], jnp.int32))[0]
    np.testing.assert_allclose(np.asarray(ab), np.asarray(mat), rtol=2e-5,
                               atol=2e-6)


def test_the_shares_add_up_to_the_uncut_layer(system):
    """Sixteen shares of one expert each, routed parts summed, plus the
    shared expert once = the layer with all sixteen held (the router ranks
    all sixteen and normalises over the chosen in every share)."""
    _, params0 = system
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((24, 64)).astype(np.float32))
    n = TINY["router_experts"]
    router = {"kernel": rng.standard_normal((64, n)).astype(np.float32)}
    experts = {k: (0.2 * rng.standard_normal(s)).astype(np.float32)
               for k, s in (("gate", (n, 64, 32)), ("up", (n, 64, 32)),
                            ("down", (n, 32, 64)))}
    with jax.default_matmul_precision("highest"):
        whole = ref.routed_experts(x, router, experts, TINY, (0, n))
        parts = sum(ref.routed_experts(
            x, router, {k: v[e:e + 1] for k, v in experts.items()}, TINY,
            (e, 1)) for e in range(n))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(whole).max()) > 0.1


# ------------------------------------------------------------------ kernel
@pytest.mark.parametrize("block_size", [8, 16])
@pytest.mark.parametrize("table_width", [3, 8, 20, 40])
def test_flash_decode_latent_read_over_tiles(table_width, block_size):
    """``flash_decode``'s latent form in interpret mode — 8 query heads a
    row against ONE stored row a key, the value the row's first 32 lanes —
    at slots of 0 keys, 1, one less than / exactly / one more than a tile,
    and the full extent: a free slot comes back as exact zeros."""
    from flexflow_tpu.kernels.flash_decode import (flash_decode_pool,
                                                   tile_blocks)

    rng = np.random.default_rng(table_width * 100 + block_size)
    heads, width, v_lanes, lanes = 8, 40, 32, 128
    extent = table_width * block_size
    tile = block_size * tile_blocks((1, 1, block_size, lanes), 4,
                                    table_width)
    n_keys = np.asarray(sorted({0, 1, max(tile - 1, 1), tile,
                                min(tile + 1, extent), extent}), np.int32)
    S = len(n_keys)
    pool = np.zeros((1 + S * table_width, 1, block_size, lanes), np.float32)
    pool[..., :width] = rng.standard_normal(pool.shape[:-1] + (width,))
    tables = np.zeros((S, table_width), np.int32)
    for s_, n in enumerate(n_keys):
        used = -(-int(n) // block_size)
        tables[s_, :used] = 1 + s_ * table_width + np.arange(used)
    q = rng.standard_normal((S, heads, width)).astype(np.float32)
    out = np.asarray(flash_decode_pool(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
        jnp.asarray(n_keys), sm_scale=0.2, interpret=True, v_lanes=v_lanes))
    assert out.shape == (S, heads, v_lanes) and np.all(np.isfinite(out))
    assert np.all(out[n_keys == 0] == 0.0), "a slot of no keys is zeros"
    ext = pool[tables][:, :, 0].reshape(S, extent, lanes)
    s = np.einsum("shw,snw->shn", q, ext[..., :width]) * 0.2
    seen = np.arange(extent)[None, None, :] < n_keys[:, None, None]
    p = np.where(seen, np.exp(np.where(seen, s, -1e30) - np.where(
        seen, s, -1e30).max(-1, keepdims=True)), 0.0)
    want = np.einsum("shn,snv->shv", p / np.maximum(
        p.sum(-1, keepdims=True), 1e-30), ext[..., :v_lanes])
    live = n_keys > 0
    np.testing.assert_allclose(out[live], want[live], atol=3e-6)


@pytest.mark.parametrize("start,n_new", [(0, 16), (37, 10), (70, 3),
                                         (255, 16)])
def test_flash_decode_chunk_read_is_causal_over_its_rows(start, n_new):
    """The kernel's chunk read (``tokens`` = 4 positions a grid step, one
    shared table row) in interpret mode: row i of the chunk sees the keys up
    to position start + i, a slot of pad rows is exact zeros, over the edge
    of the 256-key tile."""
    from flexflow_tpu.kernels.flash_decode import flash_decode_pool

    rng = np.random.default_rng(start + n_new)
    c, t, heads, width, v_lanes, bs, mb, lanes = 16, 4, 8, 40, 32, 8, 36, 128
    pool = np.zeros((mb + 4, 1, bs, lanes), np.float32)
    pool[..., :width] = rng.standard_normal(pool.shape[:-1] + (width,))
    q = rng.standard_normal((c, heads, width)).astype(np.float32)
    table = rng.permutation(np.arange(1, mb + 4))[:mb][None].astype(np.int32)
    first = np.arange(c // t) * t
    n_keys = np.where(first < n_new, start + first + 1, 0).astype(np.int32)
    out = np.asarray(flash_decode_pool(
        jnp.asarray(q).reshape(c // t, t * heads, width), jnp.asarray(pool),
        jnp.asarray(table), jnp.asarray(n_keys), sm_scale=0.2,
        interpret=True, v_lanes=v_lanes, tokens=t)).reshape(c, heads, v_lanes)
    ext = pool[table[0]][:, 0].reshape(mb * bs, lanes)
    s = np.einsum("chw,nw->chn", q, ext[:, :width]) * 0.2
    seen = np.arange(mb * bs)[None, None, :] <= (
        start + np.arange(c))[:, None, None]
    p = np.where(seen, np.exp(s - np.where(seen, s, -1e30).max(
        -1, keepdims=True)), 0.0)
    want = np.einsum("chn,nv->chv", p / p.sum(-1, keepdims=True),
                     ext[:, :v_lanes])
    np.testing.assert_allclose(out[:n_new], want[:n_new], atol=3e-6)
    assert np.all(out[np.repeat(first >= n_new, t)] == 0.0)
    assert np.all(np.isfinite(out))


#: sha256 of ``str(jaxpr)`` of ``_flash_decode_pool`` at the ``gpt2-xl-chat``
#: cell's shapes (64 slots, 25 heads of 64, 1,400 blocks of 16, a 64-wide
#: table, bf16) under jax 0.9.0: the latent and chunk forms are
#: specialisations chosen by ``v_lanes`` / ``tokens``, and a call without
#: them must trace to the program GPT-2 XL runs — the kernel of PR 45, a
#: grid step a slot with the slot's key tiles in a loop.
GPT2_XL_DECODE_DIGEST = \
    "6fd336c630c19a601b86a7799c6470258ce27eb584a55979db557afd0a9c5d2e"


def test_gpt2_xl_decode_kernel_traces_as_before():
    from flexflow_tpu.kernels.flash_decode import _flash_decode_pool

    def call(q, pool, tables, n_keys):
        return _flash_decode_pool(q, pool, tables, n_keys, sm_scale=0.125,
                                  scales=None, interpret=False)

    text = str(jax.make_jaxpr(call)(
        jax.ShapeDtypeStruct((64, 25, 64), jnp.bfloat16),
        jax.ShapeDtypeStruct((1400, 25, 16, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((64, 64), jnp.int32),
        jax.ShapeDtypeStruct((64,), jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest() \
        == GPT2_XL_DECODE_DIGEST, (
            f"digest taken with jax 0.9.0, this is jax {jax.__version__}")


# ---------------------------------------------------- the pool, in place
DEPTH, SLOTS, POOL_BLOCKS, LONG = 3, 8, 4096, 256
POOL = f"bf16[{POOL_BLOCKS},1,16,640]"


@pytest.fixture(scope="module")
def programs():
    """The four serving programs of a latent model at the published row
    (512 | 64 -> 640 lanes, bf16, 16-token blocks), lowered for a described
    v5e with the kernels' gates answering as on a TPU."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from flexflow_tpu.kernels import _common
    from flexflow_tpu.serving import ServingEngine

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    ff = build(pangu_config(batch_size=8, hidden=256, num_heads=8,
                            q_rank=128, kv_rank=512, nope_dim=128,
                            rope_dim=64, v_dim=128, intermediate=256,
                            moe_intermediate=128, vocab_size=512),
               argv=["--compute-dtype", "bf16", "--param-dtype", "bf16",
                     "--only-data-parallel", "--mesh-shape", "1"])
    eng = ServingEngine(ff, n_slots=SLOTS, max_decode_len=LONG,
                        kv_block_size=16, kv_pool_blocks=POOL_BLOCKS,
                        buckets=(64,), prefill_chunk_tokens=64)

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    i32 = on(jnp.int32(0))
    x = on(jnp.zeros((1, 64), jnp.int32))
    cache = jax.eval_shape(eng._prefill_fn(64), ff.params, [x],
                           on(jnp.ones((1,), jnp.int32)))[2]
    eng._ensure_state(cache)
    state, last = on(eng.state), on(eng._last_tokens)
    row = on(jnp.zeros((eng.max_blocks_per_slot,), jnp.int32))
    params = on(ff.params)
    real_on_tpu = _common.on_tpu
    _common.on_tpu = lambda: True
    try:
        yield {
            "decode_step": (eng._decode_fn(), (
                params, [on(jnp.zeros((SLOTS, 1), jnp.int32))], state)),
            "slot_write": (eng._write_slot_program(), (
                state, last, on(cache), i32, i32, i32, row)),
            "chunk_step": (eng._chunk_fn(64), (
                params, [x], state, row, i32, i32)),
            "cow_clone": (eng._cow_clone_program(), (state, i32, i32)),
        }
    finally:
        _common.on_tpu = real_on_tpu
        jax.config.update("jax_enable_compilation_cache", before)


@pytest.mark.parametrize("name", ["decode_step", "slot_write",
                                  "chunk_step", "cow_clone"])
def test_latent_pool_is_written_in_place(programs, name):
    """tests/test_kv_pool_in_place.py's method for the latent layout:
    every pool leaf is aliased onto an output, nothing of a leaf's shape
    is a ``copy``, and the decode step holds ``flash_decode`` and
    ``kv_write`` (the chunk step ``kv_write``)."""
    fn, args = programs[name]
    text = fn.trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    entry = text[text.index("\nENTRY "):]
    pools = {int(n) for n in re.findall(
        r"= " + re.escape(POOL) + r"\{[^}]*\} parameter\((\d+)\)", entry)}
    assert len(pools) == DEPTH, pools
    header = text[:text.index("\n")]
    start = header.index("input_output_alias={")
    aliased = {int(n) for n in re.findall(
        r"\}: \((\d+), ", header[start:header.index(" }", start)])}
    assert pools <= aliased, f"{name}: a pool leaf is not aliased in place"
    shaped = set(re.findall(
        r"= " + re.escape(POOL) + r"\{[^}]*\} ([\w-]+)\(", text))
    # a ``bitcast`` moves nothing; a ``copy`` is a pass over the leaf
    assert shaped <= {"parameter", "custom-call", "fusion", "scatter",
                      "dynamic-update-slice", "bitcast"}, \
        f"{name} rewrites the pool whole: {shaped}"
    kernels = {m.group(1) for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               for m in [re.search(r"(\w+)\)*/pallas_call", line)] if m}
    if name == "decode_step":
        assert {"flash_decode", "kv_write"} <= kernels
    if name == "chunk_step":
        assert {"kv_write", "latent_chunk_attention"} <= kernels


# -------------------------------------------------- the benchmark's copy
@pytest.fixture(scope="module")
def blocked():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_pangu", os.path.join(
            HERE, "..", "benchmark", "reference",
            "openpangu-ultra-moe-718b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_blocked_reference_equals_the_plain_one(system, blocked):
    """Head groups, query-row blocks, the experts' scan, column blocks of
    the dense MLP and row blocks of the head, at blocks smaller than the
    sequence: the same logits, with the tie handling off."""
    ff, params0 = system
    seq = np.zeros(48, np.int32)
    seq[:37] = ids(8, 37)
    blocked.QUERY_BLOCK, blocked.MLP_BLOCK, blocked.LOGIT_BLOCK = 16, 32, 16
    blocked.HEAD_GROUP = 2
    got = blocked.Reference(ff.params, TINY, route_tie=0.0).logits(seq)
    np.testing.assert_allclose(got, reference_logits(params0, seq),
                               rtol=1e-5, atol=2e-5)


def test_a_routing_tie_returns_the_row_nearer_the_programs_token(
        system, blocked):
    """With every 8th/9th pair called a tie, a checked position's row is
    the base row or the alternative's — whichever puts the next id of the
    sequence nearer the best — and positions outside the last
    ``TIE_WINDOW`` before the padding keep the reference's own routing."""
    ff, params0 = system
    seq = np.zeros(48, np.int32)
    seq[:37] = ids(8, 37)
    base = reference_logits(params0, seq)
    r = blocked.Reference(ff.params, TINY, route_tie=1.0)
    got = r.logits(seq)
    assert r.tie_counts["evaluated_twice"] > 0
    moved = np.flatnonzero(np.abs(got - base).max(axis=1) > 1e-4)
    assert r.tie_counts["took_other"] >= len(moved) > 0
    assert moved.min() >= 37 - blocked.TIE_WINDOW and moved.max() < 36
    for p in moved:
        nxt = seq[p + 1]
        assert got[p].max() - got[p][nxt] < base[p].max() - base[p][nxt]


# --------------------------------------------------------- bf16 at rest
def test_bf16_at_rest_never_builds_a_float32_tree(monkeypatch):
    """``--param-dtype bf16``: every floating leaf rests in bf16, and while
    ``compile()`` makes the tree the live device bytes never reach half of
    what the float32 tree would take (sampled as each leaf is asked for: a
    float32 copy of the whole would show as twice the tree at rest)."""
    import gc

    from flexflow_tpu.execution import executor as ex

    gc.collect()
    floor = sum(a.nbytes for a in jax.live_arrays())
    peak = [0]
    real = jax.random.fold_in

    def sampling_fold_in(key, i):
        peak[0] = max(peak[0], sum(a.nbytes for a in jax.live_arrays()))
        return real(key, i)

    monkeypatch.setattr(ex.jax if hasattr(ex, "jax") else jax.random,
                        "fold_in", sampling_fold_in, raising=False)
    monkeypatch.setattr(jax.random, "fold_in", sampling_fold_in)
    cfg = pangu_config(hidden=256, intermediate=1024, moe_intermediate=256,
                       vocab_size=4096)
    ff = build(cfg, argv=["--compute-dtype", "bf16", "--param-dtype",
                          "bf16"])
    leaves = jax.tree.leaves(ff.params)
    assert all(a.dtype == jnp.bfloat16 for a in leaves)
    at_rest = sum(a.nbytes for a in leaves)
    assert at_rest > 8e6
    assert peak[0] - floor <= 1.1 * at_rest < 2 * at_rest
    # and the default is today's: float32 masters, one program
    assert all(a.dtype == jnp.float32
               for a in jax.tree.leaves(build(pangu_config()).params))


# ------------------------------------------------- pricing a cached token
def test_a_cached_latent_token_is_priced_at_its_row():
    """``kvcache`` and ``serving/search.py`` price a latent node's token at
    its stored row (576 -> 640 lanes), once whatever the heads; an MHA node
    of the same heads costs 64 times that."""
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.ops.base import op_class_for
    from flexflow_tpu.serving import kvcache
    from flexflow_tpu.serving.search import _attention_state_bytes

    assert kvcache.latent_lanes(576) == 640
    assert kvcache.latent_token_bytes(576, 2) == 1280
    latent = op_class_for(OperatorType.OP_LATENT_ATTENTION)(
        "l0_mla", {"num_heads": 128, "kv_rank": 512, "rope_dim": 64,
                   "q_rank": 1536, "nope_dim": 128, "v_dim": 128,
                   "embed_dim": 7680, "rope_theta": 1e4, "eps": 1e-5},
        DataType.DT_BFLOAT16)
    mha = op_class_for(OperatorType.OP_MULTIHEAD_ATTENTION)(
        "l0_attn", {"num_heads": 128, "kdim": 192, "vdim": 128,
                    "embed_dim": 7680}, DataType.DT_BFLOAT16)
    assert kvcache.node_token_bytes(latent) == 1280
    assert kvcache.node_token_bytes(mha) == 128 * 320 * 2 == 64 * 1280

    class Node:
        op = latent

    assert _attention_state_bytes(Node, slots=64, max_len=13184) \
        == 64 * 13184 * 1280
