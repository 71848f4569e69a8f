"""Process start to the first timed request: import, data from the seed,
upload, the first submit of each query (compile or cache load), warm
rounds. The reference runs after the window and is not in it."""


def read(ctx):
    return ctx["setup_s"]
