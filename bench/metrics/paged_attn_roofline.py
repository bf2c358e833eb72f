"""Share of its roofline that the paged decode-attention kernel reached:
the least time the cell's chips need for the attention the decode steps
required (q, the output, and the keys and values of each live sequence's
kv_len positions, all layers; 4 H D FLOPs per position), at ``chips``
times one chip's peaks, over the device time of the ops named
``paged_decode_attention_kernel`` (averaged over the chips).  The work
is counted from the decode inputs, so it reads the same whatever
implements it."""
from counts import least_time, paged_attention_work
from devtrace import kernel_time

KERNEL = "paged_decode_attention_kernel"


def read(run):
    t = kernel_time(run.trace, KERNEL)
    if not t or not run.decode_calls or run.peak is None:
        return None
    need = 0.0
    for kv_lens in run.decode_calls:
        flops, bytes_ = paged_attention_work(run.dims, kv_lens)
        need += least_time(flops, bytes_, run.peak, run.chips)[0]
    return need / t * 100.0
