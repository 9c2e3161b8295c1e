"""Scheduler launch until the deploy plan reads COMPLETE over its HTTP API."""


def read(run):
    return run["deploy_plan_s"]
