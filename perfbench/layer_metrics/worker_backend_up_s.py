"""`startup.backend_up`: around `claim_devices()`, the TPU backend coming up."""
from perfbench.harness.startup import phase_s


def read(run):
    return phase_s(run, "backend_up")
