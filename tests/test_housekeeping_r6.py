"""Round-6 satellite fixes (ADVICE r5): TASO loader dst-side PM_* policy,
attention's single live-dropout gate."""
import json

import numpy as np
import pytest

from flexflow_tpu.ffconst import DataType


# ------------------------------------------------- substitution PM_* policy
def _load_rule(tmp_path, src_ops, dst_ops):
    from flexflow_tpu.search.substitution import load_substitution_json

    rule = {"rule": [{"name": "r", "srcOp": src_ops, "dstOp": dst_ops}]}
    p = tmp_path / "rules.json"
    p.write_text(json.dumps(rule))
    return load_substitution_json(str(p))


def test_dst_semantic_pm_without_template_rejects_rule(tmp_path):
    """A dst op carrying a semantics-bearing PM_* key (PM_PERM here) with
    NO same-type src template would be built with DEFAULT attrs — the
    loader must skip the rule like an unknown PM_ACTI instead of silently
    dropping the key (ADVICE r5)."""
    xfers = _load_rule(
        tmp_path,
        src_ops=[{"type": "OP_LINEAR", "input": [{"opId": -1, "tsId": 0}],
                  "para": []}],
        dst_ops=[{"type": "OP_TRANSPOSE",
                  "input": [{"opId": -1, "tsId": 0}],
                  "para": [{"key": "PM_PERM", "value": 5}]}])
    assert xfers == []


def test_dst_semantic_pm_with_template_still_parses(tmp_path):
    """With a same-type src op, the dst op inherits the MATCHED node's real
    attrs (not defaults), so a restated structural key stays droppable and
    the rule converts — this is what keeps the TASO collection loading."""
    xfers = _load_rule(
        tmp_path,
        src_ops=[{"type": "OP_CONCAT",
                  "input": [{"opId": -1, "tsId": 0}, {"opId": -2, "tsId": 0}],
                  "para": [{"key": "PM_AXIS", "value": 2}]}],
        dst_ops=[{"type": "OP_CONCAT",
                  "input": [{"opId": -1, "tsId": 0}, {"opId": -2, "tsId": 0}],
                  "para": [{"key": "PM_AXIS", "value": 2}]}])
    assert len(xfers) == 1


def test_dst_semantic_pm_differing_from_template_rejects(tmp_path):
    """A dst value that DIFFERS from the same-type src template's (the rule
    deliberately changes the attr, e.g. a new transpose perm) cannot be
    satisfied by attrs inheritance — the rule must be rejected, not built
    with the OLD value (review follow-up on the r6 policy)."""
    xfers = _load_rule(
        tmp_path,
        src_ops=[{"type": "OP_TRANSPOSE",
                  "input": [{"opId": -1, "tsId": 0}],
                  "para": [{"key": "PM_PERM", "value": 1}]}],
        dst_ops=[{"type": "OP_TRANSPOSE",
                  "input": [{"opId": -1, "tsId": 0}],
                  "para": [{"key": "PM_PERM", "value": 3}]}])
    assert xfers == []


def test_dst_shape_enforced_pm_still_drops(tmp_path):
    """Shape-enforced keys (PM_NUMDIM & co) are re-checked structurally by
    the pattern edges and apply()'s output-shape assert — they keep
    dropping even on a template-less dst op."""
    xfers = _load_rule(
        tmp_path,
        src_ops=[{"type": "OP_LINEAR", "input": [{"opId": -1, "tsId": 0}],
                  "para": []}],
        dst_ops=[{"type": "OP_LINEAR", "input": [{"opId": -1, "tsId": 0}],
                  "para": []},
                 {"type": "OP_RELU", "input": [{"opId": 0, "tsId": 0}],
                  "para": [{"key": "PM_NUMDIM", "value": 2}]}])
    assert len(xfers) == 1


def test_src_constraints_keep_dropping_structural_keys(tmp_path):
    """src-side PM_* constraints only narrow matching; dropping them widens
    it and soundness is kept by the output-shape check — the r6 policy
    change must not start rejecting src-side keys."""
    xfers = _load_rule(
        tmp_path,
        src_ops=[{"type": "OP_LINEAR", "input": [{"opId": -1, "tsId": 0}],
                  "para": [{"key": "PM_PERM", "value": 3}]}],
        dst_ops=[{"type": "OP_LINEAR", "input": [{"opId": -1, "tsId": 0}],
                  "para": []}])
    assert len(xfers) == 1
    assert "PM_PERM" not in xfers[0].src[0].attr_constraints


# --------------------------------------------- attention live-dropout gate
def _mha_op(dropout=0.5):
    from flexflow_tpu.ops.attention import MultiHeadAttentionOp

    return MultiHeadAttentionOp(
        "attn", {"embed_dim": 8, "num_heads": 2, "dropout": dropout,
                 "use_flash": False},
        DataType.DT_FLOAT, num_inputs=3)


def _mha_params(op, in_shapes):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ffconst import dtype_to_jnp

    key = jax.random.PRNGKey(0)
    return {name: init(key, shape, dtype_to_jnp(dt))
            for name, (shape, dt, init)
            in op.weight_specs(in_shapes).items()}


def test_einsum_fallback_passes_resolved_live_dropout(monkeypatch):
    """ops/attention.py:137 — the einsum fallback must consume the
    already-resolved live_dropout (single gate), not re-derive gating from
    raw attrs: with training=True but no rng, mha_core receives
    dropout=0.0 and rng=None after the loud warning."""
    import jax.numpy as jnp

    from flexflow_tpu.ops import attention
    from flexflow_tpu.ops.base import OpContext

    op = _mha_op(dropout=0.5)
    x = jnp.ones((2, 4, 8), jnp.float32)
    params = _mha_params(op, [x.shape] * 3)
    seen = {}
    real = attention.mha_core

    def spy(q, k, v, **kw):
        seen.update(kw)
        return real(q, k, v, **kw)

    monkeypatch.setattr(attention, "mha_core", spy)
    with pytest.warns(UserWarning, match="WITHOUT dropout"):
        op.forward(params, [x, x, x], OpContext(training=True, rng=None))
    assert seen["dropout"] == 0.0
    assert seen["rng"] is None

    # live path: training + rng -> the resolved rate and the rng ride along
    import jax

    seen.clear()
    op.forward(params, [x, x, x],
               OpContext(training=True, rng=jax.random.PRNGKey(1)))
    assert seen["dropout"] == 0.5
    assert seen["rng"] is not None

    # eval: resolved to 0.0, rng withheld
    seen.clear()
    op.forward(params, [x, x, x],
               OpContext(training=False, rng=jax.random.PRNGKey(1)))
    assert seen["dropout"] == 0.0
    assert seen["rng"] is None
