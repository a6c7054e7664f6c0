"""Decode programs the device ran per ``engine.step`` span in the trace:
one per active batch bucket until ticks batch across buckets."""


def read(run):
    if run.red is None:
        return None
    ticks = run.red.host_counts.get("engine.step", 0)
    return run.red.module_count("jit_decode_step") / ticks if ticks else None
