"""Process start to window start: data build, freeze, warm-up and compiles."""


def read(run):
    return run.setup_s
