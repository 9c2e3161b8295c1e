"""Expert groups that held at least one live assignment, a decode call an
expert layer: the window's `loop.moe_groups_touched_sum` (counted on the
device, fetched with each step's tokens) over `loop.decode_calls` x the
expert layers the worker's `/stats` `model` states.  Of E experts a step of
R live rows choosing k touches about E (1 - (1 - k/E)^R): the bytes of a
step move with the rows in flight.  The two counters are a step apart (a
call is counted when it is dispatched, its groups when its tokens are
read), one call in some thousands.  Nothing where the program has no such
counter or states no experts."""
from perfbench.harness.counters import delta, ratio


def expert_layers(run):
    """Layers with a mixture, as the worker says it built them."""
    model = (run.get("final_stats") or {}).get("model") or {}
    if not model.get("n_experts") or "n_layers" not in model:
        return None
    return model["n_layers"] - model.get("n_dense_layers", 0)


def read(run):
    layers = expert_layers(run)
    if not layers:
        return None
    calls = delta(run, "loop", "decode_calls")
    return ratio(delta(run, "loop", "moe_groups_touched_sum"),
                 (calls or 0) * layers)
