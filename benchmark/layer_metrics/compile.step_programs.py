"""Compile layer: distinct streaming-aggregate step programs the process
has bound (gauge fused_step_programs_total, exec/fused.py), loaded or
compiled: each is a program of one unrolled body per window slot, the
largest single share of a cold set-up's compile bill. It should follow
the batch capacities of the cell's tables and never their batch count."""


def read(ctx):
    from spark_rapids_tpu.obs import gauges
    return gauges.snapshot().get("fused_step_programs_total")
