"""The ``gigachat35-reasoning-2k`` cell's files at the rehearsal size
(``gigachat-tiny-reasoning``, CPU): the tree passes through the unedited
serve driver with the ``open_loop`` generator — bucketed prefill handing the
matrix state on at the last real token and the latent rows to the pool,
decode over both beside each other — the new readers read the program's
counters, and the cell's files agree with each other, with the builder, with
the catalog's row and with the issue's traffic. The rehearsal computes in
float32 (as ``olmo-hybrid-tiny-assist`` does, for the reason written there:
what it holds is the harness's path)."""
import json
import os
import sys

from benchmark.tests.test_rehearsal import ROOT, result, run

BENCH = os.path.join(ROOT, "benchmark")


def read(sub, name):
    with open(os.path.join(BENCH, sub, f"{name}.json")) as f:
        return json.load(f)


def test_the_tree_runs_the_cell_and_the_new_readers_read():
    proc = run("gigachat-tiny-reasoning", 1, seconds="2")
    line = result(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert {"hybrid_state_gb", "batch_occupancy", "check_logit_gap_max",
            "decode_tick_ms_p50"} <= set(line["metrics"])
    # 8 slots x 4 mixers x (4 x 16 x 16 f32 + 3 x 128 f32)
    assert line["metrics"]["hybrid_state_gb"]["value"] * 1e9 \
        == 8 * 4 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert "check tokens_within_reference_gap: True" in proc.stdout
    # the device-trace readers find no device on the CPU and say nothing
    assert not {"hybrid_gdn_mixer_ms_per_step", "hybrid_gdn_state_roofline",
                "hybrid_gdn_prefill_roofline", "hybrid_latent_ms_per_step",
                "hybrid_latent_read_roofline",
                "hybrid_expert_layer_ms_per_step",
                "hybrid_expert_matmul_roofline"} & set(line["metrics"])


def test_the_cells_files_agree():
    sys.path.insert(0, ROOT)
    config = read("configs", "gigachat35-432b-a28b")
    cell = read("workloads", "gigachat35-reasoning-2k")
    mix = read("traffic", "reasoning-2k")
    assert config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace",
        "full_attention_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["full_attention_layers"], config["n_routed_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) \
        == (5, 1, [4], 16, 16032, 0)
    # one leading dense layer, then one whole period as published: three
    # delta-rule layers and the latent layer that closes it
    period = config["published"]["full_attention_layers"]
    assert period[0] == 3 and all(b - a == 4 for a, b in zip(period,
                                                            period[1:]))
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert config["experts_held"] == [0, 16] and config["router_experts"] \
        == config["published"]["n_routed_experts"] == 256
    for key in ("norm_gain", "latent_gate", "softmax_mscale",
                "linear_attention", "beta", "swiglu_limit", "router",
                "weights"):
        assert config["assumed"][key], key
    assert "16 chips share each layer" in config["deployment"]
    # the issue's traffic: the mix file as it stands
    assert mix["generator"] == "open_loop"
    assert mix["prompt_len"] == {"median": 512, "sigma": 0.7, "min": 64,
                                 "max": 2048}
    assert mix["output_len"] == {"median": 2048, "sigma": 0.6, "min": 256,
                                 "max": 6144}
    eng = cell["engine"]
    assert eng["max_decode_len"] == mix["max_total_tokens"] == 8192
    assert eng["buckets"] == [256, 512, 1024, 2048]
    assert eng["n_slots"] == 128 and eng["kv_pool_blocks"] == 32769
    assert (cell["kind"], cell["chips"]) == ("serve", 1)
    assert cell["limits"] == {"ttft_ms": 5000, "token_gap_ms": 5000}
    assert cell["trace_steps"] == 192
    # no chunking, no prefix cache: a recurrent state has neither
    flags = config["compile_flags"] + cell["compile_flags"]
    assert "--prefill-chunk-tokens" not in flags
    assert flags[flags.index("--prefix-cache") + 1] == "off"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]].count(cell["name"]) == 1
    assert len(bench["workloads"]) == 9 and len(bench["configs"]) == 7
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    for m in bench["per_layer"]:
        if m["name"].startswith("hybrid_"):
            assert m["workloads"] == [cell["name"]] \
                and m["moves"] == "tpot_p50_ms"


def test_the_catalogs_numbers_stand():
    """Every number of the catalog row's ``config`` is in the file under the
    same key, but the keys listed as reduced (the row's numbers are copied
    here: the catalog lies outside the repository)."""
    catalog = {
        "vocab_size": 128256, "max_position_embeddings": 262144,
        "hidden_size": 7168, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "num_hidden_layers": 40,
        "num_attention_heads": 64, "n_shared_experts": 1,
        "n_routed_experts": 256, "routed_scaling_factor": 2.5,
        "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "qk_nope_head_dim": 128, "qk_head_dim": 192,
        "n_group": 1, "topk_group": 1, "num_experts_per_tok": 8,
        "first_k_dense_replace": 3, "num_key_value_heads": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 100000,
        "layernorm_gating_weight": 2, "linear_key_head_dim": 128,
        "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
        "linear_num_key_heads": 32, "linear_num_value_heads": 64,
        "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-06,
        "swiglu_limit": 10, "num_nextn_predict_layers": 2}
    config = read("configs", "gigachat35-432b-a28b")
    for key, value in catalog.items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 32768,
        "type": "yarn"}
    assert config["published"]["full_attention_layers"] == list(
        range(3, 40, 4))
    assert (config["norm_type"], config["layernorm_type"]) \
        == ("ZeroCenteredGatedNorm", "pre_post")
    assert config["linear_gating_type"] \
        == "gated_rmsnorm_sigmoid_zero_centered"
    assert config["rope_interleave"] and config["gated_attention"] \
        and config["use_mla_scaling_factor"]
    assert config["use_shared_expert_sigmoid"] is False
    assert config["tie_word_embeddings"] is False
