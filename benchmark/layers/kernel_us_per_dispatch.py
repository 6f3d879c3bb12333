"""Mean device time of one quorum program, from the device trace: the sparse
step, the dense step (what a round that carries reads runs) and the fused
rounds, whichever the traced stretch dispatched."""

KERNELS = ("quorum_step_impl", "quorum_step_dense_impl",
           "quorum_multiround_impl")


def read(ctx):
    t = ctx.trace
    if not t or not t.get("kernel_n"):
        return None
    n = sum(t["kernel_n"].values())
    return sum(t["kernel_s"].values()) / n * 1e6
