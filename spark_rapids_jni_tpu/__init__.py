"""spark_rapids_jni_tpu — a TPU-native acceleration layer for Apache Spark.

Brand-new framework with the capability surface of spark-rapids-jni
(surveyed in SURVEY.md): JCUDF row<->column transcode, ANSI string casts,
Spark-bug-compatible DECIMAL128 arithmetic, DeltaLake Z-order, parquet
footer pruning, plus the cuDF-tier operator set (sort, filter, hash
aggregate, join, expression eval) — all re-designed for TPU: jax/XLA for
the compute path, ``shard_map`` + ICI collectives for exchange, and a C++
runtime for handles/host-buffers/JNI.

int64 lanes are required throughout (Spark longs, DECIMAL64, JCUDF row
offsets), so x64 mode is enabled at import, before any tracing happens.
"""

import os as _os

if (
    _os.environ.get("SRJT_LOCKDEP", "").lower() in ("1", "true", "yes")  # srjt-lint: allow-environ(bootstrap: lockdep must patch threading before ANY package module creates a lock; importing utils.knobs here would import the whole utils tree first)
    or _os.environ.get("SRJT_RACE", "").lower() in ("1", "true", "yes")  # srjt-lint: allow-environ(bootstrap: the race detector rides the lockdep shim and has the same patch-before-any-lock constraint)
):
    from .analysis import lockdep as _lockdep

    _lockdep.install()

import jax

jax.config.update("jax_enable_x64", True)

from .utils import compile_cache as _compile_cache  # noqa: E402

_compile_cache.configure()

from . import columnar  # noqa: E402,F401
from .columnar import Column, DType, Table, TypeId  # noqa: E402,F401

__version__ = "0.1.0"
