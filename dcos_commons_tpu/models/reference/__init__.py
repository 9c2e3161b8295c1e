"""Plain references of the architectures the serving path supports: the
published equations in ``jax.numpy`` and float32, no cache, no kernels,
nothing imported from the program.  The tests hold the program to them
at small sizes; the benchmark keeps its own copy of each beside the
family that uses it (``perfbench/families/<family>/reference.py``)."""
