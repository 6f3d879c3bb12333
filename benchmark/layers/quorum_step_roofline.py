"""The quorum programs' share of the memory roofline: the least time the
chip could take for their dispatches (state read once and written once,
``roofline.py``) over the device time they took.  Bound: memory."""

KERNELS = ("quorum_step_impl", "quorum_step_dense_impl",
           "quorum_multiround_impl")


def read(ctx):
    t = ctx.trace
    if not t or not t.get("kernel_n"):
        return None
    n = sum(t["kernel_n"].values())
    least = n * ctx.roofline.dispatch_min_seconds(
        ctx.state_leaves, ctx.device_kind
    )
    return 100.0 * least / sum(t["kernel_s"].values())
