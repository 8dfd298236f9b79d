"""Wall the dataloader's producer thread spent in ``device_put_batch`` per
step (the program's ``batch_put`` spans: one batch from host memory onto the
devices, as the step shards it), inside the window."""
NAME = "input_put_ms_per_step"
UNIT = "ms/step"
LAYER = "data pipeline"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    from benchmark.reduce import program_spans
    return program_spans.per_step_ms(run, 'batch_put', 'other_s')
