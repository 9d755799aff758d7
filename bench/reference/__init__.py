"""Plain float32 references for the benchmark's correctness comparison.

Written from the published model descriptions in straightforward
``jax.numpy``; nothing here imports the program. Each family module
(``qwen2``, ``mamba2``) reads its sizes from the benchmark's configuration
file and exposes ``arch(cfg)``, ``leaf_table(arch)`` and
``forward(params, tokens, arch, num)``.
"""
