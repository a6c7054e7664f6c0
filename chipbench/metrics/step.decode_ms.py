"""Mean device time of one decode program, from the trace."""


def read(run):
    if run.red is None:
        return None
    n = run.red.module_count("jit_decode_step")
    return run.red.module_seconds("jit_decode_step") / n * 1e3 if n else None
