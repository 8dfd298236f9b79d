"""Record the two small host traces that ``test_program_spans.py`` checks the
program-span readers on. They hold host threads only, so the CPU records
them; run again when the program's spans or the trace format change::

    JAX_PLATFORMS=cpu python benchmark/tests/record_spans_fixture.py

``tiny_fit_cpu.xplane.pb``: a two-layer MLP through ``FFModel.fit``, two
passes of four steps inside the benchmark's ``bench_window`` span.
``tiny_serve_cpu.xplane.pb``: a two-layer GPT-2 (hidden 64) serving six
short prompts through ``ServingEngine.generate``. Each stays under 1 MB.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.argv = sys.argv[:1]  # FFConfig() reads sys.argv


def record(name: str, fn) -> None:
    import jax

    out = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # as the benchmark traces: host spans only
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench_window"):
        fn()
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                "*.xplane.pb"))[0]
    dst = os.path.join(HERE, "fixtures", name)
    shutil.copy(pb, dst)
    shutil.rmtree(out)
    print(f"record_spans_fixture: wrote {dst} ({os.path.getsize(dst)} bytes)")


def main() -> None:
    import numpy as np

    from flexflow_tpu import (ActiMode, AdamOptimizer, FFConfig, FFModel,
                              LossType, SGDOptimizer)
    from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
    from flexflow_tpu.serving import ServingEngine

    config = FFConfig()
    config.batch_size = 32
    ff = FFModel(config)
    t = ff.create_tensor((32, 256))
    t = ff.softmax(ff.dense(ff.dense(t, 64, ActiMode.AC_MODE_RELU), 4))
    ff.compile(optimizer=AdamOptimizer(ff, alpha=0.01),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 256)).astype(np.float32)
    y = rng.integers(0, 4, size=(128,)).astype(np.int32)
    ff.fit(x, y, epochs=1)  # compiles
    record("tiny_fit_cpu.xplane.pb", lambda: ff.fit(x, y, epochs=2))

    cfg = GPT2Config(batch_size=8, seq_len=64, hidden=64, num_heads=4,
                     num_layers=2, intermediate=128, vocab_size=100)
    config = FFConfig()
    config.batch_size = cfg.batch_size
    gpt = FFModel(config)
    build_gpt2(gpt, cfg)
    gpt.compile(optimizer=SGDOptimizer(gpt),
                loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    prompts = [rng.integers(1, 99, size=int(rng.integers(3, 8))).tolist()
               for _ in range(6)]
    eng = ServingEngine(gpt, n_slots=3, max_decode_len=64, kv_block_size=8)
    eng.generate(prompts[:2], max_new_tokens=3)  # compiles
    record("tiny_serve_cpu.xplane.pb",
           lambda: eng.generate(prompts, max_new_tokens=6))


if __name__ == "__main__":
    main()
