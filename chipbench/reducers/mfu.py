"""Model FLOP/s utilisation, in percent: the family's analytic FLOPs per
item (recomputed work not counted) times the items a chip completed per
second in the steady untraced stretch, over the chip's published bf16 peak."""


def reduce(measured, params):
    if measured.peaks is None:
        return None
    return (100.0 * measured.cell.family.flops_per_item()
            * measured.throughput_per_chip / measured.peaks[0])
