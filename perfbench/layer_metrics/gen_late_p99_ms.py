"""How late the load generator sent: send time - due time, 99th percentile."""
from perfbench.harness import metrics
from perfbench.harness.readers import judged


def read(run):
    late = [1e3 * (o["sent"] - o["due"]) for o in judged(run)]
    return metrics.percentile(late, 99) if late else None
