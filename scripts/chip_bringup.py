"""On-chip bring-up runs that are too long for ``chip_smoke.py``.

Run through the chip tool, one process per call, sections by name::

    python scripts/chip_bringup.py longctx opcost      # one chip
    python scripts/chip_bringup.py fourchip            # the four-chip host

* ``longctx``  — the flash kernels past the fused backward's residency
  budget: the s4096 b1 8-layer BERT block trained through compile()+fit(),
  and causal forward+backward at s8192 (fused-schedule boundary) and s16384
  (two-pass streaming) at (1, 2, s, 64) bf16, blocks (512, 1024).
* ``opcost``   — ``Simulator.measure_operator_cost`` read five times from
  cold on BERT-Large's op shapes: the spread is what a calibrated search
  would inherit.
* ``fourchip`` — BERT-Large at batch 32 on four chips, data-parallel and
  under the searched plan (``--budget 30 --enable-parameter-parallel
  --enable-attribute-parallel``): mesh, plan, falling loss, four distinct
  devices in every parameter's and the batch's shards, peak bytes per chip.

Like chip_smoke.py it refuses to run without a known TPU, and any failed
check is a non-zero exit. Step times are information, not metrics.
"""
from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chip_smoke import (build_trainer, check,  # noqa: E402
                        check_environment, device_batch, fit_repeated, info,
                        mosaic_calls, on_distinct_devices, synthetic_batch,
                        train_step_text)


def _check_losses(label, losses):
    import numpy as np

    check(bool(np.all(np.isfinite(losses))) and losses[-1] < losses[0],
          f"{label}: loss finite and falling "
          f"({', '.join(f'{v:.4f}' for v in losses)})")


# ------------------------------------------------------------------ longctx
def longctx() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.kernels.flash_attention import (_bwd_blocks,
                                                      flash_attention)
    from flexflow_tpu.models.bert import BertConfig

    cfg = BertConfig(batch_size=1, seq_len=4096, hidden=1024, num_heads=16,
                     num_layers=8, intermediate=4096)
    ff = build_trainer(cfg, [])
    batch = synthetic_batch(cfg)
    losses, walls = fit_repeated(ff, cfg, batch, 6)
    _check_losses("s4096 b1 8-layer", losses)
    calls = mosaic_calls(train_step_text(ff, batch))
    check({"flash_attention_fwd", "flash_attention_bwd_fused"} <= calls,
          f"s4096 train step runs flash forward and the fused backward at "
          f"k-tile {_bwd_blocks(512, 1024, None, None, 4096, 4096, 64)} "
          f"({sorted(calls)})")
    info(f"s4096 b1 8-layer: first step {walls[0]:.1f} s, steady step "
         f"{1e3 * float(np.median(walls[2:])):.1f} ms")
    del ff

    rng = np.random.default_rng(3)
    for s, want in ((8192, {"flash_attention_bwd_fused"}),
                    (16384, {"flash_attention_bwd_dkv",
                             "flash_attention_bwd_dq"})):
        q, k, v = (jnp.asarray(rng.normal(size=(1, 2, s, 64)), jnp.bfloat16)
                   for _ in range(3))

        def loss(q, k, v):
            o = flash_attention(q, k, v, True, 512, 1024)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        calls = mosaic_calls(g.lower(q, k, v).compile().as_text())
        check({"flash_attention_fwd"} | want <= calls,
              f"causal s{s}: compiles to {sorted(calls)}")
        check(all(bool(jnp.all(jnp.isfinite(t.astype(jnp.float32))))
                  for t in g(q, k, v)),
              f"causal s{s}: forward+backward gradients finite")


# ------------------------------------------------------------------- opcost
def opcost() -> None:
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.bert import BertConfig, build_bert
    from flexflow_tpu.search.machine_model import TPUMachineModel
    from flexflow_tpu.search.simulator import OpSharding, Simulator

    cfg = BertConfig(batch_size=8, seq_len=512, hidden=1024, num_heads=16,
                     num_layers=1, intermediate=4096)
    config = FFConfig()
    config.parse_args(["-b", "8"])
    ff = FFModel(config)
    build_bert(ff, cfg)
    pcg = ff.create_pcg()
    worst = 0.0
    for name in ("l0_attn", "l0_fc1", "l0_ln1"):
        node = next(n for n in pcg.compute_nodes()
                    if n.name.startswith(name))
        in_shapes = [pcg.nodes[g].out_shapes[i] for g, i in node.inputs]
        for direction in ("fwd", "grad"):
            reads = []
            for _ in range(5):  # a fresh simulator: nothing cached
                sim = Simulator(TPUMachineModel.detect(1))
                reads.append(sim.measure_operator_cost(
                    node, in_shapes, compute_dtype=jnp.bfloat16,
                    direction=direction))
            med = float(np.median(reads))
            spread = (max(reads) - min(reads)) / med
            worst = max(worst, spread)
            est = sim.op_cost(node, in_shapes, OpSharding()).forward_time
            info(f"measure_operator_cost {name} {direction}: "
                 f"{', '.join(f'{1e6 * r:.1f}' for r in reads)} us; "
                 f"(max-min)/median {spread:.1%}; analytic fwd "
                 f"{1e6 * est:.1f} us")
    check(worst <= 0.05,
          f"measure_operator_cost repeats within 5% on every op and "
          f"direction (worst {worst:.1%})")


# ----------------------------------------------------------------- fourchip
def fourchip() -> None:
    import jax
    import numpy as np

    from flexflow_tpu.models.bert import BertConfig

    n = len(jax.devices())
    check(n == 4, f"four chips visible (found {n})")
    cfg = BertConfig(batch_size=32, seq_len=512, hidden=1024, num_heads=16,
                     num_layers=24, intermediate=4096)
    plans = (("data-parallel", ["--only-data-parallel"]),
             ("searched", ["--budget", "30", "--enable-parameter-parallel",
                           "--enable-attribute-parallel"]))
    for label, argv in plans:
        t0 = time.perf_counter()
        ff = build_trainer(cfg, argv)
        compile_s = time.perf_counter() - t0
        used = int(ff.mesh.devices.size)
        info(f"{label}: mesh {dict(ff.mesh.shape)} plan "
             f"[{ff.strategy.describe()}] compile() {compile_s:.1f} s")
        if used < n:
            info(f"{label}: the plan's mesh uses {used} of {n} chips — "
                 f"{n - used} idle")
        batch = synthetic_batch(cfg)
        losses, walls = fit_repeated(ff, cfg, batch, 6)
        _check_losses(label, losses)
        info(f"{label}: first step {walls[0]:.1f} s, steady step "
             f"{1e3 * float(np.median(walls[2:])):.1f} ms "
             f"({cfg.batch_size / float(np.median(walls[2:])):.1f} "
             f"samples/s, each step synced for the loss)")
        if ff._pipeline_trainer is not None:
            info(f"{label}: trains through PipelineTrainer "
                 f"(pp, dp, n_micro) = {ff.strategy.pipeline}")
        check(on_distinct_devices(jax.tree_util.tree_leaves(ff.params)
                                  + device_batch(ff, batch)[0]) == used,
              f"{label}: every parameter and the batch have shards on "
              f"{used} distinct devices")
        peaks = [d.memory_stats()["peak_bytes_in_use"]
                 for d in jax.devices()]
        check(all(p > 2 ** 30 for p in peaks[:used]),
              f"{label}: peak_bytes_in_use per chip "
              f"{[f'{p / 2 ** 30:.2f} GiB' for p in peaks]}")
        del ff


SECTIONS = {"longctx": longctx, "opcost": opcost, "fourchip": fourchip}


def main(argv) -> None:
    unknown = [a for a in argv if a not in SECTIONS]
    if unknown or not argv:
        sys.exit(f"usage: chip_bringup.py {{{'|'.join(SECTIONS)}}}...")
    sys.argv = sys.argv[:1]  # FFConfig() reads sys.argv; these are not flags
    check_environment()
    from flexflow_tpu.obs import enable as obs_enable

    obs_enable()  # per-step losses and walls come from fit()'s telemetry
    for name in argv:
        info(f"---- {name}")
        SECTIONS[name]()
    print("chip_bringup: all sections passed", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
