"""Share of the hops' per-edge products formed on the device (%): the
program's ``hop.product_edges_device`` over it plus
``hop.product_edges_host``, in edges, warm-up included.  100 means no
hop formed an edge-sized product on the host."""
import program_spans


def read(ctx):
    device = program_spans.counter("hop.product_edges_device") or 0
    host = program_spans.counter("hop.product_edges_host") or 0
    if not device + host:
        return None
    return 100 * device / (device + host)
