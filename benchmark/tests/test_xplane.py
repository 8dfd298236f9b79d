"""The reduction from a trace to metrics: the interval arithmetic on
synthetic intervals, the naming rules on event texts as the v5e writes them,
and the whole reduction pinned on a small trace recorded on the chip."""
import os

import pytest

from benchmark.reduce import xplane as X

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "tiny_bert_v5e.xplane.pb")


def test_union_total_clip():
    u = X.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert u == [(0, 3), (5, 8)]
    assert X.total(u) == 6
    assert X.clip(u, (2, 6)) == [(2, 3), (5, 6)]


def test_subtract_and_gaps():
    assert X.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert X.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert X.subtract([(0, 4)], []) == [(0, 4)]
    assert X.gaps([(1, 2), (4, 6)], (0, 8)) == [(0, 1), (2, 4), (6, 8)]
    assert X.gaps([(0, 8)], (0, 8)) == []


def test_exposed_collective_time():
    # an all-reduce in flight 10..30; compute covers 0..18 and 26..40:
    # exposed is 18..26
    assert X.exposed([(10, 30)], [(0, 18), (26, 40)]) == 8
    assert X.exposed([(10, 30)], [(0, 40)]) == 0          # fully hidden
    assert X.exposed([(10, 30), (20, 35)], []) == 25      # nothing hides it


def test_gap_attribution_prefers_cover_then_innermost():
    spans = [("fit_epoch", 0, 100), ("generator_sleep", 40, 60),
             ("tick", 55, 58)]
    assert X.attribute_gap((42, 50), spans) == "generator_sleep"
    assert X.attribute_gap((10, 20), spans) == "fit_epoch"
    assert X.attribute_gap((56, 57), spans) == "tick"
    assert X.attribute_gap((200, 210), spans) == "host_untraced"


KERNEL = ('%flash_attention_bwd_fused.3 = (bf16[4,4,512,64]{3,2,1,0:T(8,128)'
          '(2,1)S(1)}, bf16[4,4,512,64]{3,2,1,0}) custom-call(u32[1]{0} %c), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints={}')


@pytest.mark.parametrize("text,want", [
    (KERNEL, ("kernel", "flash_attention_bwd_fused")),
    ("%all-reduce.5 = f32[1024,256]{1,0} all-reduce(f32[1024,256]{1,0} %x), "
     "replica_groups={}", ("collective", "all-reduce")),
    ("%all-reduce-start.2 = f32[8]{0} all-reduce-start(f32[8]{0} %x)",
     ("collective", "all-reduce")),
    ("%all-gather-done.1 = f32[8]{0} all-gather-done(f32[8]{0} %s)",
     ("collective", "all-gather")),
    ("%fusion.340 = (bf16[1024]{0}, bf16[4,512,1024]{2,1,0}) fusion(bf16[4]{0}"
     " %p), kind=kOutput, calls=%fused_computation.1", ("xla", "fusion")),
    ("%copy-done.26 = f32[256,4,64]{0,2,1} copy-done((f32[256,4,64]{0,2,1}, "
     "u32[]) %copy-start.26)", ("xla", "copy-done")),
])
def test_classify(text, want):
    assert X.classify(text) == want


def test_scopes_from_compiled_text():
    text = '''
  %fusion.7 = bf16[4,512,1024]{2,1,0} fusion(%a), kind=kOutput, calls=%f, metadata={op_name="jit(step)/jit(main)/jvp(l3_fc1_24)/dot_general" source_file="x.py"}
  ROOT %fusion.9 = f32[8]{0} fusion(%b), kind=kLoop, calls=%g, metadata={op_name="jit(step)/jit(main)/transpose(jvp(l11_attn_77))/einsum"}
  %add.1 = f32[] add(%c, %d), metadata={op_name="jit(step)/jit(main)/add"}
  %h.2 = f32[8]{0} multiply(%c, %d), metadata={op_name="jit(decode)/h7_ln1_52/mul"}
'''
    assert X.scope_map(text) == {"fusion.7": "l_fc1", "fusion.9": "l_attn",
                                 "h.2": "h_ln1"}
    assert X.node_scope("jit(step)/pool/reduce_sum") is None


def test_reduction_on_the_recorded_trace():
    r = X.reduce_trace(FIXTURE, span_names=["fit_epoch", "generator_sleep"],
                       window_span="bench_window")
    assert r["n_devices"] == 1 and r["worst_device"] == 0
    # every op is one of kernel / collective / xla, and they do not overlap
    parts = sum(r["kernel_s"].values()) + sum(r["collective_s"].values()) \
        + r["xla_s"]
    assert parts == pytest.approx(r["busy_s"], rel=1e-6)
    pinned = PINNED
    assert r["busy_s"] == pytest.approx(pinned["busy_s"], rel=1e-6)
    assert r["window_s"] == pytest.approx(pinned["window_s"], rel=1e-6)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(
        pinned["idle_share"], abs=1e-6)
    assert r["kernel_s"]["flash_attention_fwd"] == pytest.approx(
        pinned["flash_attention_fwd_s"], rel=1e-6)
    assert r["modules"]["jit_step"]["count"] == pinned["steps"]
    assert r["longest_gaps"][0][0] == "generator_sleep"
    assert r["idle_gaps"][0][0] == pinned["largest_idle_span"]


# measured once on the recorded trace (tests/record_fixture.py, 1 x v5e)
PINNED = {"busy_s": 0.00057198, "window_s": 0.061841243,
          "idle_share": 0.9907508327, "flash_attention_fwd_s": 5.9555e-05,
          "steps": 4, "largest_idle_span": "generator_sleep"}
