"""Process start until the worker is deployed, warm and checked."""


def read(run):
    return run["setup_s"]
