"""Wall the dataloader's producer thread spent in the source iterator per
step (the program's ``batch_gather`` spans: the numpy gather of one batch),
inside the window. Gather + put above the step time is a producer that cannot
keep up; ``input_wait_ms_per_step`` then shows on the main thread."""
NAME = "input_gather_ms_per_step"
UNIT = "ms/step"
LAYER = "data pipeline"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    from benchmark.reduce import program_spans
    return program_spans.per_step_ms(run, 'batch_gather', 'other_s')
