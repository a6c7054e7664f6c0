"""Set-up: process start to the window's opening (imports, weights,
server, warm-up, compiles or compile-cache loads)."""


def read(run):
    return run.setup_s
