"""The masked KSP2 re-solve's share of its memory roofline: the least
bytes of the window's re-solves (cellbench.work_ksp2, one per KSP2
destination per topology event, from the events and the configuration)
at the chip's HBM bandwidth, over the device time of
``jit_batched_spf_distances_masked`` in the trace.  None where the
kernel did not run in the window."""

from cellbench import work_ksp2

KERNEL = "jit_batched_spf_distances_masked"


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    kernel_s = dict(tr["device_ops"]).get(KERNEL)
    if not kernel_s:
        return None
    world = ctx["world"]
    lsdb = world.lsdb
    dests = len(world.ksp2_nodes() - {world.me})
    nbytes = work_ksp2.ksp2_bytes(ctx["result"]["events"], dests,
                                  directed_edges=2 * len(lsdb.links),
                                  nodes=len(lsdb.names))
    return 100.0 * nbytes / ctx["peak"]["hbm_bytes_per_s"] / kernel_s
