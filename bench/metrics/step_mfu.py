"""The whole dSVB step's share of the chip's bf16 peak, from the trace:
the fleet iterations the device ran inside the traced window, counted
from the slice program's executions on the "XLA Modules" line (each one
`slice_iters` iterations over all `max_fleet` slots), times the
operations of one session-iteration (VBE kernel, VBM post-stage,
diffusion combine; `bench/work.py`), over the window.  Counted from the
program and not from the kernel, it stays readable when a later change
takes the kernel off the path.  The program computes in f32 at "highest"
(about six bf16 passes per product), so its ceiling lies far below 100%."""
from bench import trace, work

SLICE = "jit_slice_fn("          # the serving driver's compiled slice


def read(ctx):
    runs = trace.module_runs(ctx["trace"], lambda n: n.startswith(SLICE))
    if runs <= 0:
        return None
    c, tr = ctx["config"], ctx["traffic"]
    iters = runs * tr["slice_iters"] * tr["max_fleet"]
    flops = iters * work.step_flops(c["nodes"], max(c["points_per_node"]),
                                    c["K"], c["D"])
    lo, hi = trace.window(ctx["trace"])
    return 100.0 * flops / ((hi - lo) / 1e9 * ctx["peaks"]["flops_bf16"])
