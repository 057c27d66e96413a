"""Model FLOP/s utilization of the whole served step in a saturated cell:
model FLOPs of the requests completed in the traced window over the
window's length times the chip's bf16 peak, in percent."""


def read(run):
    if run.device is None:
        return None
    window = run.device.window_s
    done = sum(1 for r in run.reqs
               if r.done_s is not None and not r.failed and r.done_s <= window)
    if not done:
        return None
    return 100.0 * done * run.flops_per_request / (window * run.peaks["bf16_flops"])
