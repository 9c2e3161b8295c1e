"""The worker's own `warm_s`: compiling or reading its two programs and running each once."""


def read(run):
    return run["final_stats"].get("warm_s")
