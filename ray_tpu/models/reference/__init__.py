"""Plain references of the architectures models/llama.py runs: the published equations
in straightforward jax.numpy, float32, matrix products at the highest precision, no
kernel, no remat, no cache. They read the program's parameter tree (the names
`llama.init` gives its leaves) and a plain dict of the ModelConfig's fields, and import
nothing of the program. One module an architecture family."""
