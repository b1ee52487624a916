"""mfu.batch: useful model operations of the window (bench/flops.py) over
the device's busy seconds in the traced window (bench/trace.py, averaged
over chips) x chips x the chip's peak bf16 FLOP/s, in %.  Dividing by busy
time rather than the window keeps host time out: it is the model step's
own share of the peak."""


def read(run):
    tr = run["trace"]
    if run["peaks"] is None or tr is None or tr["busy_s"] <= 0:
        return None
    peak = run["peaks"]["bf16_flops"] * run["chips"]
    return 100.0 * run["flops"]() / (tr["busy_s"] * peak)
