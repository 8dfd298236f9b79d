"""OSDI'22 artifact protocol smoke (reference: scripts/osdi22ae/*.sh — the
searched-vs-data-parallel comparison that is the reproducible baseline,
BASELINE.md)."""
import os
import sys

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts", "osdi22ae")


def test_protocol_runs_both_modes():
    sys.path.insert(0, SCRIPTS)
    try:
        import run as osdi_run

        dp, searched = osdi_run.main(["mlp", "-b", "16", "--budget", "3",
                                      "--epochs", "1"])
    finally:
        sys.path.remove(SCRIPTS)
    assert dp["mode"] == "data_parallel" and dp["samples_per_sec"] > 0
    assert searched["mode"] == "unity_searched" \
        and searched["samples_per_sec"] > 0
    assert dp["mesh"] == {"data": 8}


def test_searched_beats_dp_in_simulation_bert_and_dlrm():
    """The artifact's headline claim (searched >= DP on the same hardware,
    scripts/osdi22ae/bert.sh + dlrm.sh) asserted on the simulator for both
    workloads; on chips the comparison is still unmeasured (ROADMAP S6:
    the one four-chip cell's searched plan is data-parallel)."""
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.models import BertConfig, build_bert, build_dlrm
    from flexflow_tpu.search.machine_model import TPUMachineModel
    from flexflow_tpu.search.simulator import OpSharding, Simulator
    from flexflow_tpu.search.unity import unity_search

    machine = TPUMachineModel.from_generation("v5e", 8)

    def check(build):
        config = FFConfig()
        config.batch_size = 16
        ff = FFModel(config)
        build(ff)
        pcg = ff.create_pcg()
        sim = Simulator(machine)
        res = unity_search(pcg.copy(), config, 8, machine=machine,
                           return_result=True, insert_ir_nodes=False)
        dp8 = {n.guid: OpSharding(dp=8) for n in pcg.compute_nodes()}
        t_dp, _ = sim.simulate(pcg, dp8)
        assert res.sim_time <= t_dp * 1.001, (res.sim_time, t_dp)
        return t_dp / res.sim_time

    check(lambda ff: build_bert(ff, BertConfig(
        batch_size=16, seq_len=128, hidden=1024, num_heads=16,
        num_layers=2, intermediate=4096)))
    # DLRM with realistic tables: the searched table sharding must win big
    ratio = check(lambda ff: build_dlrm(
        ff, batch_size=16, embedding_sizes=(100000,) * 8,
        embedding_dim=64))
    assert ratio > 1.5, f"table parallelism should beat DP clearly: {ratio}"


def test_dlrm_claim_first_principles_envelope():
    """VERDICT r3 item 5: pin dlrm_searched_vs_dp inside a justified
    bytes/bandwidth envelope so the headline cannot swing with cost-model
    edits (it went 27.5x -> 19.8x -> 7.2x across rounds while unanchored).

    Config (the seed round's DLRM chip run): batch 64, 8 tables x 200000 x 64
    f32, v5e-8 (ici 50 GB/s/link, (2,4) torus -> 4 concurrent ring links
    for the full 8-chip group; HBM 819 GB/s x 0.8 eff; Adam update moves
    ~7 bytes per weight byte — optimizer_kernel.cu analog).

    First principles, DP-8 per step:
      table grads allreduce (dense, reference optimizer_kernel.cu:88):
        wire >= 2*(7/8) * table_bytes / (4 links * 50 GB/s)
      optimizer update (every chip updates ALL replicated tables):
        wire >= 7 * table_bytes / (819 GB/s * 0.8)
    Table-parallel per step (each chip owns 1 of 8 tables, no table
    sync): update >= 7 * (table_bytes/8) / (819 GB/s * 0.8).
    """
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models import build_dlrm
    from flexflow_tpu.search.machine_model import TPUMachineModel
    from flexflow_tpu.search.simulator import OpSharding, Simulator
    from flexflow_tpu.search.unity import simulate_best, unity_search

    machine = TPUMachineModel.from_generation("v5e", 8)
    assert machine.torus == (2, 4)
    config = FFConfig()
    config.batch_size = 64
    ff = FFModel(config)
    build_dlrm(ff, batch_size=64, embedding_sizes=(200000,) * 8,
               embedding_dim=64)
    pcg = ff.create_pcg()
    sim = Simulator(machine)
    res = unity_search(pcg.copy(), config, 8, machine=machine,
                       return_result=True, insert_ir_nodes=False)
    dp8 = {n.guid: OpSharding(dp=8) for n in pcg.compute_nodes()}
    t_dp = simulate_best(sim, pcg, dp8, {})
    ratio = t_dp / res.sim_time

    # hand-computed bounds (independent arithmetic, not machine methods).
    # The grad allreduce rides ICI while the optimizer update streams HBM —
    # different wires, so they CAN fully overlap: the wall-clock floor is
    # max(...), the no-overlap ceiling sum(...) (+50% MLP/latency slack).
    table_bytes = 8 * 200000 * 64 * 4
    eff_hbm = 819e9 * 0.8
    dp_sync_wire = 2 * (7 / 8) * table_bytes / (4 * 50e9)   # ~3.58 ms
    dp_update_wire = 7 * table_bytes / eff_hbm              # ~4.38 ms
    dp_lower = max(dp_sync_wire, dp_update_wire)
    dp_upper = 1.5 * (dp_sync_wire + dp_update_wire)
    searched_lower = 7 * (table_bytes / 8) / eff_hbm        # ~0.55 ms

    assert dp_lower <= t_dp <= dp_upper, (t_dp, dp_lower, dp_upper)
    assert res.sim_time >= searched_lower, (res.sim_time, searched_lower)
    # implied envelope on the headline ratio
    assert 2.0 <= ratio <= dp_upper / searched_lower, \
        (ratio, dp_upper / searched_lower)
