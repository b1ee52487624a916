"""setup_s: seconds from process start to the opening of the window
(loading, weight generation, compilation or cache loads, warm-up)."""


def read(run):
    return run["setup_s"]
