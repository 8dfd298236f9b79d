"""Record the small device trace that ``test_xplane.py`` pins the reduction on.

Run on the chip, once, when the trace format changes::

    chiprun --chips 1 -- python benchmark/tests/record_fixture.py

A tiny BERT-proxy (1 layer, hidden 256, 4 heads of 64, seq 512, batch 4,
bf16, Adam) goes through ``FFModel.compile`` + ``fit`` so that the flash
kernels run, two passes of two steps are traced with the benchmark's own host spans
around them, and the ``.xplane.pb`` plus a by-hand description of its planes
are written under ``chiprun_out/fixture/``. Copy the ``.xplane.pb`` to
``benchmark/tests/fixtures/tiny_bert_v5e.xplane.pb`` (it must stay under 1 MB).
"""
from __future__ import annotations

import glob
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.argv = sys.argv[:1]  # FFConfig() reads sys.argv


def main() -> None:
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        sys.exit(f"record_fixture: no TPU (platform {jax.devices()[0].platform!r})")
    from benchmark.reduce.xplane import describe
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
    from flexflow_tpu.models.bert import BertConfig, build_bert

    n_dev = len(jax.devices())
    cfg = BertConfig(batch_size=4 * n_dev, seq_len=512, hidden=256, num_heads=4,
                     num_layers=1, intermediate=1024)
    config = FFConfig()
    config.parse_args(["-b", str(cfg.batch_size), "--compute-dtype", "bf16"]
                      + (["--only-data-parallel"] if n_dev > 1 else []))
    ff = FFModel(config)
    build_bert(ff, cfg)
    ff.compile(optimizer=AdamOptimizer(ff, alpha=1e-5),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2 * cfg.batch_size, cfg.seq_len, cfg.hidden)).astype(np.float32)
    y = rng.integers(0, 2, size=(2 * cfg.batch_size,)).astype(np.int32)
    ff.fit(x, y, batch_size=cfg.batch_size, epochs=1, shuffle=False)  # compiles

    out = os.path.join(ROOT, "chiprun_out", "fixture")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    import time

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # as the benchmark traces: host spans only
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench_window"):
        for epoch in range(2):
            with jax.profiler.TraceAnnotation("fit_epoch", epoch=epoch):
                ff.fit(x, y, batch_size=cfg.batch_size, epochs=1,
                       shuffle=False)
            with jax.profiler.TraceAnnotation("generator_sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
    dst = os.path.join(out, f"tiny_bert_v5e_{n_dev}chip.xplane.pb")
    shutil.copy(pb, dst)
    shutil.rmtree(os.path.join(out, "plugins"))
    with open(os.path.join(out, f"describe_{n_dev}chip.txt"), "w") as f:
        f.write(describe(dst))
    print(f"record_fixture: wrote {dst} ({os.path.getsize(dst)} bytes)")


if __name__ == "__main__":
    main()
