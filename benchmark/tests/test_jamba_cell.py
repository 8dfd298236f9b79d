"""The ``jamba2-3b-reasoning`` cell's files at the rehearsal size
(``jamba-tiny-reasoning``, CPU): the sound tree passes through the unedited
serve driver with the ``open_loop`` generator — bucketed prefill handing the
recurrent state on at the last real token, decode over the slot-major state
beside the one-head pool, held to the plain reference's full forward — the
new readers read the program's counters, and the cell's files agree with
each other, with the builder and with the issue's traffic."""
import json
import os
import sys

from benchmark.tests.test_rehearsal import ROOT, result, run

BENCH = os.path.join(ROOT, "benchmark")


def read(sub, name):
    with open(os.path.join(BENCH, sub, f"{name}.json")) as f:
        return json.load(f)


def test_the_sound_tree_passes_and_the_new_readers_read():
    proc = run("jamba-tiny-reasoning", 1, seconds="2")
    line = result(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert {"recurrent_state_gb", "batch_occupancy", "check_logit_gap_max",
            "decode_tick_ms_p50"} <= set(line["metrics"])
    # 8 slots x 6 mixers x (16 x 128 f32 + 3 x 128 bf16)
    assert line["metrics"]["recurrent_state_gb"]["value"] * 1e9 \
        == 8 * 6 * (16 * 128 * 4 + 3 * 128 * 2)
    assert "check tokens_within_reference_gap: True" in proc.stdout
    # the device-trace readers find no device on the CPU and say nothing
    assert not {"ssm_mixer_ms_per_step", "ssm_state_roofline",
                "selective_scan_roofline"} & set(line["metrics"])


def test_the_cells_files_agree():
    sys.path.insert(0, ROOT)
    from flexflow_tpu.models.jamba import JambaConfig, jamba_param_count

    config = read("configs", "ai21-jamba2-3b")
    cell = read("workloads", "jamba2-3b-reasoning")
    mix = read("traffic", "reasoning-2k")
    cfg = JambaConfig(batch_size=8, **{
        f: config[k] for f, k in config["builder"]["fields"].items()})
    assert jamba_param_count(cfg) == config["parameters_held"]
    assert config["reduced"] == []
    for key, published in config["published"].items():
        assert config[key] == published
    assert len(config["departures"]) == 1 and config["assumed"]
    # the issue's traffic, as given
    assert mix["generator"] == "open_loop"
    assert mix["prompt_len"] == {"median": 512, "sigma": 0.7, "min": 64,
                                 "max": 2048}
    assert mix["output_len"] == {"median": 2048, "sigma": 0.6, "min": 256,
                                 "max": 6144}
    eng = cell["engine"]
    assert eng["max_decode_len"] == mix["max_total_tokens"] == 8192
    assert eng["buckets"] == [256, 512, 1024, 2048]
    assert (cell["kind"], cell["chips"]) == ("serve", 1)
    # no chunking, no prefix cache: a recurrent state has neither
    flags = config["compile_flags"] + cell["compile_flags"]
    assert "--prefill-chunk-tokens" not in flags
    assert flags[flags.index("--prefix-cache") + 1] == "off"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]].count(cell["name"]) == 1
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
