"""A kernel's share of its roofline, in percent.

The least time the chip could take is the bytes the work function counts
from the shapes, over the device's peak bandwidth (`peaks.json`); the share
is that over the device time of the layer's modules.  With no time to read
there is no share: the reader returns nothing, never 0."""


def read(trace, spec: dict, ctx: dict):
    seconds = trace.module_seconds(spec["modules"])
    if seconds <= 0 or ctx["assemblies"] == 0:
        return None
    work = ctx["work"](spec["work"])
    nbytes = work.hbm_bytes(ctx["shapes"]) * ctx["assemblies"]
    return 100.0 * nbytes / (ctx["peaks"][spec["peak"]] * seconds)
