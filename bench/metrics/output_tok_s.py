"""output_tok_s: output tokens that reached the host in the window, divided
by the window's length on the host clock."""


def read(run):
    return run["samples"]["tokens"] / run["window"].seconds
