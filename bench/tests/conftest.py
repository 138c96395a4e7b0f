"""Published key names of each configuration family, for the checks of
``test_bench_files``: its table lists the families of the first
configuration; each family added since is registered here."""
from bench.tests import test_bench_files

# model_type -> (expert width, routed expert count, shared expert count)
test_bench_files.EXPERT_KEYS.setdefault(
    "mixtral", ("intermediate_size", "num_local_experts", "n_shared_experts"))
