"""The benchmark: everything BENCHMARK.json's `paths` names lives here."""
