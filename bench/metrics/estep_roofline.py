"""The fused VBE kernel's share of its roofline: the least time the chip
could take for the kernel's work (`bench/work.py`: operations at the bf16
peak or bytes at the HBM peak, whichever is longer; compute bounds it at
D=52) over the summed device time of the kernel's events in the trace.
Each event is one call over the whole fleet: every slot, every node."""
from bench import trace, work

KERNEL = "%gmm_estep_nodes"     # the HLO name of the kernel's custom call
BLOCK_T = 512                    # the fused backend's data block


def read(ctx):
    seconds, calls = trace.op_seconds(ctx["trace"],
                                      lambda n: n.startswith(KERNEL + "."))
    if not calls or seconds <= 0:
        return None
    c, p = ctx["config"], ctx["peaks"]
    n = ctx["traffic"]["max_fleet"] * c["nodes"]
    T = -(-max(c["points_per_node"]) // BLOCK_T) * BLOCK_T
    flops = calls * work.estep_flops(n, T, c["K"], c["D"])
    nbytes = calls * work.estep_bytes(n, T, c["K"], c["D"])
    least = max(flops / p["flops_bf16"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / seconds
