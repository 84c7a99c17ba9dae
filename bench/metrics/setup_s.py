"""Process start to the window's first event: data, loading, warm-up and,
in a checkout's first run, compilation."""
UNIT = "s"


def read(run):
    return run.setup_s
