"""One signature for the train step, whoever made its state (ISSUE 42,
ROADMAP.md S12 (1)). ``tests/test_program_builds.py`` holds compile(), fit
and the memory analysis; here are the other makers of an optimizer state —
a checkpoint restore, the fallback cascade's re-initialisation, the guarded
step's two branches, the pipeline trainer's stages — each read through the
build registry (``obs.builds()``), and the arithmetic: two steps from a state
born as an array against the same two steps from the bare ``0`` the counter
used to begin as. On the CPU's eight virtual devices."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import (AdamOptimizer, FFConfig, FFModel, LossType,
                          SGDOptimizer)
from flexflow_tpu import obs
from flexflow_tpu.execution.checkpoint import (restore_checkpoint,
                                               save_checkpoint)
from flexflow_tpu.parallel.strategies import hybrid_data_tensor_strategy
from flexflow_tpu.parallel.strategy import data_parallel_strategy
from flexflow_tpu.resilience import ChaosPlan, StrategyCascade

BATCH = 8
N_SAMPLES = 32  # 4 steps an epoch


def _model(optimizer=None, strategy_fn=None, layers=2, **cfg_kw):
    cfg = FFConfig()
    cfg.batch_size = BATCH
    for k, v in cfg_kw.items():
        setattr(cfg, k, v)
    ff = FFModel(cfg)
    t = ff.create_tensor((BATCH, 16), name="x")
    for i in range(layers - 1):
        t = ff.relu(ff.dense(t, 32 + 8 * i, name=f"d{i + 1}"))
    ff.dense(t, 10, name=f"d{layers}")
    ff.compile(optimizer=(optimizer or (lambda m: SGDOptimizer(
                   m, lr=0.05, momentum=0.9)))(ff),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               strategy_fn=strategy_fn)
    return ff


def _data():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(N_SAMPLES, 16)).astype(np.float32)
    y = rng.integers(0, 10, size=N_SAMPLES).astype(np.int32)
    return x, y


def _signatures(state):
    """What ``jax.jit`` keys a program on, leaf by leaf."""
    return jax.tree_util.tree_map(
        lambda a: (a.dtype, a.aval.weak_type, a.sharding, a.committed),
        state)


def _step_builds(mark, name="jit_step"):
    return obs.build_totals(mark)["by_name"].get(name, 0)


def _placed_batch(ff, x, y):
    sh = ff.executor.batch_sharding(2)
    return ([jax.device_put(x[:BATCH], sh)],
            jax.device_put(y[:BATCH].reshape(BATCH, 1), sh))


# ------------------------------------------------------- a checkpoint restore
@pytest.mark.parametrize("restore", ["sharded", "host_staged"])
def test_a_restored_state_calls_the_step_already_built(restore, tmp_path):
    """``restore_checkpoint`` restores into the live tree: on the saved
    topology shard by shard, on another one through the host. Either way the
    state it hands back calls the program the live state's first step
    built."""
    def split(dp, tp):
        return lambda pcg: hybrid_data_tensor_strategy(pcg, dp, tp)

    x, y = _data()
    saved = _model(strategy_fn=split(4, 2))
    saved.fit(x, y, epochs=1, shuffle=False)
    path = save_checkpoint(saved, str(tmp_path), step=4)

    mark = obs.build_mark()
    ff = _model(strategy_fn=split(4, 2) if restore == "sharded"
                else split(2, 2))
    born = _signatures(ff.opt_state)
    ff.fit(x[:BATCH], y[:BATCH], epochs=1)
    assert _step_builds(mark) == 1
    assert restore_checkpoint(ff, path) == 4
    assert _signatures(ff.opt_state) == born
    assert int(ff.opt_state["step"]) == 4
    ff.fit(x, y, epochs=1, shuffle=False)
    assert _step_builds(mark) == 1
    assert int(ff.opt_state["step"]) == 8


# --------------------------------------------- the fallback's re-initialisation
def test_a_state_remade_by_the_fallback_calls_the_step_its_probe_built():
    """A fallback hop compiles the model again and makes the state anew
    (``resilience/fallback.py``); the compile check's probe builds the new
    executor's step, and fit's steps after it build nothing."""
    x, y = _data()
    ff = _model(search_budget=8)
    winner = ff.strategy.describe()
    mark = obs.build_mark()
    cascade = StrategyCascade.maybe_create(ff, ChaosPlan(fail_compiles=1))
    cascade.preverify([x], ff._prep_label(y), BATCH)
    assert cascade.fallbacks == 1 and ff.strategy.describe() != winner
    assert _step_builds(mark) == 1  # the probe's, on the new executor
    born = _signatures(ff.opt_state)
    assert born["step"][:2] == (jnp.int32, False) and born["step"][3]
    ff.fit(x, y, epochs=1)
    ff.fit(x, y, epochs=1)
    assert _signatures(ff.opt_state) == born
    assert _step_builds(mark) == 1


# ------------------------------------------------------------ the guarded step
def test_the_guarded_step_builds_once_over_a_skipped_and_a_taken_update():
    """``make_train_step(guard=True)`` passes the state through ``lax.cond``:
    the skipped update hands the counter back as it came, the taken one a
    counter one higher, and both are the signature the state was born with."""
    x, y = _data()
    ff = _model(only_data_parallel=True)
    guarded = ff.executor.make_train_step(guard=True)
    bx, by = _placed_batch(ff, x, y)
    rng = jax.random.PRNGKey(0)
    born = _signatures(ff.opt_state)
    mark = obs.build_mark()
    params, state, _, _, ok = guarded(ff.params, ff.opt_state,
                                      [bx[0] * jnp.nan], by, rng)
    assert not bool(ok) and int(state["step"]) == 0
    assert _signatures(state) == born
    params, state, _, _, ok = guarded(params, state, bx, by, rng)
    assert bool(ok) and int(state["step"]) == 1
    assert _signatures(state) == born
    params, state, _, _, ok = guarded(params, state, bx, by, rng)
    assert bool(ok) and int(state["step"]) == 2
    assert _step_builds(mark) == 1


# -------------------------------------------------------- the pipeline trainer
def test_the_pipeline_trainers_stage_updates_build_once_a_stage():
    """The trainer keeps one state a stage, made by ``load_params``; a
    stage's jitted update is built at its first step and never again."""
    def pipe_strategy(pcg):
        s = data_parallel_strategy(pcg, 1)
        s.pipeline = (2, 1, 2)
        return s

    x, y = _data()
    ff = _model(strategy_fn=pipe_strategy, layers=3)
    tr = ff._pipeline_trainer
    assert tr is not None and tr.pp == 2
    mark = obs.build_mark()
    ff.fit(x[:BATCH], y[:BATCH], epochs=1)  # one step
    first = _step_builds(mark, "jit_upd")
    assert 1 <= first <= len(tr.opt_states)
    born = [_signatures(s) for s in tr.opt_states]
    for sig in born:
        assert sig["step"][:2] == (jnp.int32, False) and sig["step"][3]
    ff.fit(x, y, epochs=1)
    assert [_signatures(s) for s in tr.opt_states] == born
    assert [int(s["step"]) for s in tr.opt_states] == [5] * len(born)
    assert _step_builds(mark, "jit_upd") == first


# ------------------------------------------------------------- the arithmetic
@pytest.mark.parametrize("optimizer", ["adam", "sgd_momentum"])
def test_two_steps_are_bitwise_the_steps_from_a_bare_zero(optimizer):
    """The arithmetic did not change: until PR 42 a process's first step ran
    from a counter that was a bare ``0`` and its later steps from the array
    that step handed back. Two steps from the state as it is born now give
    the same parameters, moments and counter, bit for bit."""
    make = {"adam": lambda m: AdamOptimizer(m, alpha=0.01),
            "sgd_momentum": lambda m: SGDOptimizer(m, lr=0.05, momentum=0.9)}
    x, y = _data()
    ff = _model(optimizer=make[optimizer], only_data_parallel=True)
    step = ff.executor.make_train_step()
    bx, by = _placed_batch(ff, x, y)

    def two_steps(state):
        params = jax.tree_util.tree_map(jnp.copy, ff.params)  # donated
        for i in range(2):
            params, state, loss, _ = step(params, state, bx, by,
                                          jax.random.PRNGKey(i))
        return jax.device_get((params, state, loss))

    fresh = ff.optimizer.init_state(ff.params)
    assert fresh["step"].dtype == jnp.int32
    was = dict(ff.optimizer.init_state(ff.params), step=0)
    new, old = two_steps(fresh), two_steps(was)
    assert new[1]["step"] == old[1]["step"] == 2
    assert new[1]["step"].dtype == np.int32
    leaves_new, tree_new = jax.tree_util.tree_flatten(new)
    leaves_old, tree_old = jax.tree_util.tree_flatten(old)
    assert tree_new == tree_old and len(leaves_new) > 4
    for a, b in zip(leaves_new, leaves_old):
        assert a.dtype == b.dtype and np.array_equal(a, b)
