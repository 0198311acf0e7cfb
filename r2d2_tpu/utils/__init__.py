"""Infrastructure utilities: checkpointing, metrics, profiling, faults.

Import the submodule you need (`from r2d2_tpu.utils.checkpoint import ...`):
nothing is re-exported here, because `utils.checkpoint` imports the learner and
the learner imports `utils.profiling`."""
