"""Liveness-aware peak-memory model (VERDICT r4 item 3).

The analytic model (Simulator.simulate's memory term) must land within the
~1.25x band of XLA's compiled peak (Compiled.memory_analysis
.peak_memory_in_bytes ~= argument + temp bytes with donated outputs aliased;
reference: per-device memory validation vs the framebuffer budget,
/root/reference/src/runtime/graph.cc:1984-2032). The r4 model (sum of all
activations x2 + weights x4) overshot by 1.78x, biasing every memory-lambda
feasibility call toward false-infeasible.

The XLA peaks pinned here were measured on a v5e in 2026-07 (round 5) and
nothing measures the analytic model beside XLA's peak since (ROADMAP S6; the
benchmark's cells record peak_hbm_gb alone, PERF.md §3); CPU-compiled peaks
use a different buffer assignment and are NOT comparable, so this test
validates the analytic side against the recorded chip numbers."""
import pytest

from flexflow_tpu import AdamOptimizer, DataType, FFConfig, FFModel, LossType
from flexflow_tpu.models.bert import BertConfig, build_bert
from flexflow_tpu.search.machine_model import TPUMachineModel
from flexflow_tpu.search.simulator import OpSharding, Simulator

# heavyweight tier: excluded from the fast tier-1 gate (-m 'not slow');
# still runs in the full suite / nightly (see pyproject [tool.pytest.ini_options])
pytestmark = pytest.mark.slow


# XLA peak_memory_in_bytes, measured on v5e (2026-07, jax 0.9/libtpu of this
# image) for the exact configs built below
XLA_PEAK_MB = {
    "bert512": 6894.1,    # b8 s512 h1024 L24 bf16 + f32 Adam
    "bert4096": 2306.0,   # b1 s4096 h1024 L8 bf16 + f32 Adam
    "dlrm": 1325.7,       # 8 x 200k x 64 f32 tables + MLPs, f32 Adam
}
BAND = (0.8, 1.25)


def _analytic_mb(ff, activation_el):
    pcg = ff.pcg if ff.pcg is not None else ff.create_pcg()
    sim = Simulator(TPUMachineModel.from_generation("v5e", 1))
    sim.activation_el = activation_el
    dp1 = {n.guid: OpSharding(dp=1) for n in pcg.compute_nodes()}
    _, mem = sim.simulate(pcg, dp1, {})
    return mem / 2 ** 20


def _bert(cfg, bf16=True):
    config = FFConfig()
    config.batch_size = cfg.batch_size
    if bf16:
        config.compute_dtype = DataType.DT_BFLOAT16
    ff = FFModel(config)
    build_bert(ff, cfg)
    ff.compile(optimizer=AdamOptimizer(ff, alpha=1e-4),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


@pytest.mark.parametrize("key,cfg", [
    ("bert512", BertConfig(batch_size=8, seq_len=512, hidden=1024,
                           num_heads=16, num_layers=24, intermediate=4096)),
    ("bert4096", BertConfig(batch_size=1, seq_len=4096, hidden=1024,
                            num_heads=16, num_layers=8, intermediate=4096)),
])
def test_bert_analytic_within_band_of_chip_peak(key, cfg):
    ff = _bert(cfg)
    ratio = _analytic_mb(ff, activation_el=2) / XLA_PEAK_MB[key]
    assert BAND[0] <= ratio <= BAND[1], (key, ratio)


def test_dlrm_analytic_within_band_of_chip_peak():
    from flexflow_tpu.models.dlrm import build_dlrm

    config = FFConfig()
    config.batch_size = 64
    ff = FFModel(config)
    build_dlrm(ff, batch_size=64, embedding_sizes=(200000,) * 8,
               embedding_dim=64)
    ff.compile(optimizer=AdamOptimizer(ff, alpha=1e-3),
               loss_type=LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE)
    ratio = _analytic_mb(ff, activation_el=None) / XLA_PEAK_MB["dlrm"]
    assert BAND[0] <= ratio <= BAND[1], ratio


def test_memory_model_components():
    """Decomposition invariants: bf16 residuals halve the activation term
    but not the f32 master-weight term, and the bf16 model's total includes
    weight grads in the compute dtype (w x 3.5 under Adam, not x4)."""
    cfg = BertConfig(batch_size=4, seq_len=256, hidden=256, num_heads=4,
                     num_layers=2, intermediate=1024)
    ff = _bert(cfg)
    full = _analytic_mb(ff, activation_el=None)
    mixed = _analytic_mb(ff, activation_el=2)
    assert mixed < full
    # weights dominate this tiny-batch config: mixed precision saves the
    # activation half plus half the wgrad, so the drop stays below 50%
    assert full * 0.5 < mixed < full
