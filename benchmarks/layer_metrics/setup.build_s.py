"""Process start to the model or engine built, before any warm-up (host
clock). Moves setup_s."""


def read(run):
    return run["setup"]["build_s"]
