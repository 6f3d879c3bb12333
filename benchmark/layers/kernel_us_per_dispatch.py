"""Mean device time of one quorum program, from the device trace."""

KERNELS = ("quorum_step_impl", "quorum_multiround_impl")


def read(ctx):
    t = ctx.trace
    if not t or not t.get("kernel_n"):
        return None
    n = sum(t["kernel_n"].values())
    return sum(t["kernel_s"].values()) / n * 1e6
