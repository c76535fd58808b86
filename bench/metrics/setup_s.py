"""Process start to window start: generation, transfer, compile, warm-up."""


def read(run):
    return run.setup_s
