"""One prefill chunk's share of the roofline, for a chunk half way
through the window's mean prompt."""
from statistics import mean

from perfbench.harness.readers import family_needs, judged, roofline_share


def read(run):
    prompts = [o["prompt_tokens"] for o in judged(run)]
    if not prompts:
        return None
    chunk = run["final_stats"].get("prefill_chunk_tokens", 64)
    needs = family_needs(run).prefill_chunk(
        run["model"], chunk, mean(prompts) / 2.0
    )
    return roofline_share(run, needs, "jit__prefill")
