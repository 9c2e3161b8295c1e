"""The scheduler's own start: its process's OS start until it hands the launch to the agent."""
from perfbench.harness.startup import startup


def read(run):
    stamps = startup(run)
    launched, began = stamps.get("launched"), stamps.get("scheduler_started")
    if launched is None or began is None:
        return None
    return launched - began
