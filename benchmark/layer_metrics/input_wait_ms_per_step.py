"""Wall the train loop's main thread spent waiting for its next batch, per
step: the program's ``dataloader_wait`` spans (each ``q.get()`` of
``prefetch_iterator``'s consumer) inside the window, summed, over the
window's steps. While it waits the device has nothing queued, so this is the
input pipeline's share of the idle gaps; 0 is a producer that keeps up."""
NAME = "input_wait_ms_per_step"
UNIT = "ms/step"
LAYER = "data pipeline"
MOVES = "train_tokens_per_s"
CELLS = ["*"]


def read(run):
    from benchmark.reduce import program_spans
    return program_spans.per_step_ms(run, 'dataloader_wait', 'main_s')
