"""Environment toggles + small host utilities.

Parity with ml/pkg/util/utils.go:10-50: DEBUG_ENV, LIMIT_PARALLELISM, and a
free-port finder.
"""

import os
import socket

# The compile cache's home when JAX_COMPILATION_CACHE_DIR does not place
# it: a FIXED path inside the checkout. The directory is part of the
# cache key's lookup, so one that moves (a temp home, a pid, a time)
# never hits across processes.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache for this process;
    returns the directory in use, or None when it is off.

    Elastic parallelism re-lowers the round program whenever the round
    shape changes, and a cold ResNet-18 round compiles for minutes on a
    v5e; with the cache on, each (program, shape) pays XLA compilation
    once per host — later processes deserialize the executable instead.
    The reference never needed this because Fission functions are
    eagerly-executed torch (no compile step at all).

    Placement is the OPERATOR's: if JAX_COMPILATION_CACHE_DIR is set,
    JAX already points there and the directory is left alone; otherwise
    the cache goes to DEFAULT_COMPILE_CACHE_DIR. Either way only the two
    admission thresholds are set here. Called once at each process
    entry (cli main, jobserver main, chip_smoke.py, bench.py) so train
    and serve programs alike are cached. Idempotent. Opt out with
    KUBEML_COMPILE_CACHE=0 (e.g. for compile-time benchmarking).
    """
    if os.environ.get("KUBEML_COMPILE_CACHE", "").lower() in ("0", "false",
                                                              "no"):
        return None
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_COMPILE_CACHE_DIR
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.set_cache_dir(path)
    # default thresholds skip sub-second programs; the round program's
    # *steady* recompiles are the target, so keep a small floor to avoid
    # churning the cache with trivial host-side jits (loss reducers etc.)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def is_debug_env() -> bool:
    return os.environ.get("DEBUG_ENV", "").lower() in ("1", "true", "yes")


def limit_parallelism() -> bool:
    """When set, jobs ignore scheduler parallelism updates
    (reference gate: ml/pkg/train/job.go:210-213)."""
    return os.environ.get("LIMIT_PARALLELISM", "").lower() in ("1", "true", "yes")


def parse_env_spec(spec: str) -> dict:
    """'K=V[;K2=V2]' -> env dict. ';' separates the pairs so VALUES may
    contain commas — device lists like TPU_VISIBLE_DEVICES=0,1 are the
    primary use (--job-partition)."""
    out = {}
    for pair in spec.split(";"):
        pair = pair.strip()
        if not pair:
            continue
        if "=" not in pair:
            raise ValueError(f"bad env spec {pair!r}: expected KEY=VALUE")
        k, v = pair.split("=", 1)
        out[k.strip()] = v
    return out


def find_free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
