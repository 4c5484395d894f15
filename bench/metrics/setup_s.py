"""Seconds from the start of the benchmark process to the first timed
call: JAX and chip start-up, the plan (from the plan cache or built),
the executor (from the compile cache or compiled) and one warm call."""


def read(run):
    return run["setup_s"]
