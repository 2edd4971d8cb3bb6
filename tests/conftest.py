import jax

from repro import compile_cache

# GP-core numerics are validated against dense float64 oracles; model smoke
# tests use explicit dtypes so the global x64 flag does not affect them.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: the suite is compile-bound on CPU, so
# repeat runs (local dev, CI retries) skip most of the compile cost. The
# directory is JAX_COMPILATION_CACHE_DIR when set, else <repo>/.jax_cache.
compile_cache.configure()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
