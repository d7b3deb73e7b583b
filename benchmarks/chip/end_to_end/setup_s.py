"""setup_s: start of the process to the start of the window (host clock):
interpreter and JAX start, corpus, engine build, warm-up, compiles."""


def read(run):
    return run.setup_s
