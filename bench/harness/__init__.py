"""The benchmark harness: data, window, checks, trace reduction."""

import os


def use_checkout_caches(bench_dir: str) -> None:
    """Point JAX's persistent compilation cache, the program's
    characterization cache and the reference's table cache at fixed
    directories under ``<bench_dir>/.cache``, and keep the TPU runtime
    from writing its logs to a fixed path outside the checkout.  Call it
    before anything imports JAX: JAX reads its cache directory when it
    starts."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache = os.path.join(bench_dir, ".cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache, "jax")
    os.environ["REPRO_CHAR_CACHE_DIR"] = os.path.join(cache, "char")
    os.environ["BENCH_REFERENCE_CACHE_DIR"] = os.path.join(cache,
                                                           "reference")
