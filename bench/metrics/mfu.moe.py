"""mfu.moe: useful model operations of the window in a cell whose expert
layer holds a share of the experts (bench/moe_flops.py: attention, router
and head from the served tokens, the held experts from the engine's
``moe_local_assignments`` counter) over the device's busy seconds in the
traced window (bench/trace.py, averaged over chips) x chips x the chip's
peak bf16 FLOP/s, in %.  None where the program has no such counter."""


def read(run):
    from bench import moe_flops
    tr = run["trace"]
    if run["peaks"] is None or tr is None or tr["busy_s"] <= 0:
        return None
    flops = moe_flops.window_flops(run["cell"].config, run["window"])
    if flops is None:
        return None
    peak = run["peaks"]["bf16_flops"] * run["chips"]
    return 100.0 * flops / (tr["busy_s"] * peak)
