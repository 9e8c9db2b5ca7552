"""Share of ``segment_reduce``'s full grid of 8-row windows that its
calls reduced (%): the program's ``kernel.segment_reduce_windows`` over
``kernel.segment_reduce_grid_windows``, warm-up included.  100 means
every call walked every window of every (segment tile, row block)
cell."""
import program_spans


def read(ctx):
    windows = program_spans.counter("kernel.segment_reduce_windows")
    grid = program_spans.counter("kernel.segment_reduce_grid_windows")
    if windows is None or not grid:
        return None
    return 100 * windows / grid
