"""A kernel's share of its roofline: the least time the chip could take for
the kernel's calls in one step — the larger of operations over the peak
FLOP/s and bytes over the peak bytes/s, both from the family's
``kernel_costs()[params["cost"]]`` — over the device time of the operations
matching ``patterns``.  Prints which of the two bounds it."""

from chipbench.reducers import trace_op_ms


def reduce(measured, params):
    cost = measured.cell.family.kernel_costs().get(params["cost"])
    if measured.trace is None or cost is None or measured.peaks is None:
        return None
    ms = trace_op_ms.reduce(measured, params)
    if not ms:
        return None
    flops, nbytes = cost
    compute_ms = flops / measured.peaks[0] * 1e3
    memory_ms = nbytes / measured.peaks[1] * 1e3
    print(f"chipbench: {params['cost']} roofline: compute {compute_ms:.3f} "
          f"ms, memory {memory_ms:.3f} ms, measured {ms:.3f} ms -> "
          f"{'compute' if compute_ms >= memory_ms else 'memory'}-bound",
          flush=True)
    return 100.0 * max(compute_ms, memory_ms) / ms
