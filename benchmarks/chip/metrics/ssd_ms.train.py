"""Device time per step, in ms, of the Pallas SSD scan kernels of the
mamba2 mixer, forward and backward: the summed duration of the events
found by the name the kernels carry in a TPU trace (their jitted
wrapper's name, ``ssd_scan_pallas``), per step of the traced window and
per device. The kernels run inside the phases, so this time is also part
of ``extract_ms.train`` and ``tune_ms.train``. Nothing where no such
kernel ran: a model with no SSD layers, or a program whose scan is XLA's."""
from chipbench import xplane

KERNEL = "ssd_scan_pallas"


def per_step(ctx):
    """(kernel events, device seconds) per step and device, or None."""
    trace, steps = ctx["trace"], ctx["steps"]
    if not steps or not trace.n_devices:
        return None
    n, secs = xplane.matching(trace, ctx["lo"], ctx["hi"], (KERNEL,))
    if n == 0 or secs <= 0:
        return None
    per = steps * trace.n_devices
    return n / per, secs / per


def read(ctx):
    got = per_step(ctx)
    return None if got is None else 1e3 * got[1]
