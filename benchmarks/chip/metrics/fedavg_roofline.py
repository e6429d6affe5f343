"""The batched FedAvg kernel's share of its roofline, in %: the least
time the chip could take for the launches in the traced study, over
their device time.  Per launch the kernel reads the (R, N, P) fp32
round state and the (R, N) weights and writes the (R, P) means, and
does 2 R N P operations; the least time is the larger of those bytes
over HBM bandwidth and the operations over the bf16 peak (at these
shapes it is the bytes, by far).  The device time is the kernel's
(``fedavg_batched_pallas``) plus its wrapper's ``pad`` of the round
state to a multiple of the kernel's tile: the pad is what reads the
state from HBM, and the kernel then reads the padded copy from VMEM."""

import devtrace

KERNEL = "fedavg_batched_pallas"


def launch_cost(r: int, n: int, p: int):
    """(bytes, ops) of one launch at the cell's shapes."""
    return 4 * (r * n * p + r * n + r * p), 2 * r * n * p


def read(rec):
    t = rec["device_trace"]
    if t is None or rec["peak"] is None:
        return None
    seconds, launches = devtrace.kernel_time(t, KERNEL)
    if not launches:
        return None
    nbytes, ops = launch_cost(rec["requesters"], rec["traffic"]["method"]["n_max"],
                              rec["params"])
    least = max(nbytes / rec["peak"]["hbm_bytes_per_s"],
                ops / rec["peak"]["bf16_flops"])
    return 100.0 * launches * least / seconds
