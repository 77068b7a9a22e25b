"""A kernel's share of its roofline, in percent: the least time the chip
could take for the bytes and operations ``benchmark/kernels.py`` counts
from the shapes, over the kernel's device time in the trace.
``kernel`` names the counting functions (``<kernel>_bytes``, optional
``<kernel>_ops``); ``module`` the jitted program in the trace."""


def read(ctx, kernel, module):
    red = ctx.get("trace")
    if not red:
        return None
    from benchmark import xplane

    seconds = xplane.kernel_seconds(red, module=module)
    if seconds <= 0:
        return None
    k = ctx["kernels"]
    nbytes = getattr(k, kernel + "_bytes")(ctx["config"], ctx["codec"])
    ops = getattr(k, kernel + "_ops", lambda *_: 0)(ctx["config"],
                                                    ctx["codec"])
    return k.roofline_share(nbytes, ops, seconds, ctx["device"]["kind"])
