"""Share of ``coo_spmm``'s full grid that its calls ran (%): the
program's ``kernel.coo_spmm_steps`` over ``kernel.coo_spmm_grid_steps``,
warm-up included.  100 means every call visited every (output tile, edge
block, child-row tile) cell."""
import program_spans


def read(ctx):
    steps = program_spans.counter("kernel.coo_spmm_steps")
    grid = program_spans.counter("kernel.coo_spmm_grid_steps")
    if steps is None or not grid:
        return None
    return 100 * steps / grid
