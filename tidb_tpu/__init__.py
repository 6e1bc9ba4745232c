"""tidb_tpu — a TPU-native relational execution framework.

A from-scratch rebuild of the capabilities of the reference SQL layer
(PiotrNewt/tidb, a TiDB fork): MySQL-dialect SQL front-end, rule-based
planner, columnar vectorized executor, hash aggregation/join, distributed
execution — redesigned for TPU hardware on JAX/XLA/Pallas rather than
ported from the reference's Go/goroutine architecture.

Layer map (mirrors SURVEY.md section 1's layer map of the reference):

  session/      -- Session.execute() parse->plan->run loop, sysvars
  parser/       -- MySQL-dialect SQL -> AST          (ref: parser/)
  planner/      -- logical/physical plans, rules     (ref: planner/core)
  expression/   -- expr trees -> jitted columnar fns (ref: expression/ VecEval*)
  executor/     -- pull-based operators over chunks  (ref: executor/)
  ops/          -- device kernels: filter/agg/join   (ref: hot loops of executor/)
  chunk/        -- columnar batch format             (ref: util/chunk)
  storage/      -- host columnar partitions, catalog (ref: store/mockstore, kv/)
  parallel/     -- mesh, shard_map fragments, exchange (ref: distsql/, store/copr)
  utils/        -- memory tracking, tracing          (ref: util/memory, util/execdetails)

Design rules (TPU-first):
  * all device shapes are static; row liveness is a selection mask
  * strings are sorted-dictionary int32 codes (order-preserving)
  * decimals are scaled int64
  * no data-dependent Python control flow under jit
"""

import os

import jax

# 64-bit types are required for decimal (scaled int64) and SUM accumulators.
# Must run before any jnp array is created anywhere in the package.
jax.config.update("jax_enable_x64", True)

# Persistent compilation cache, one for every process of this checkout
# (server, tests, benchmark, chip_smoke). Where JAX_COMPILATION_CACHE_DIR is
# set jax reads it itself and nothing here names a directory; otherwise
# the cache lives at <checkout>/.jax_cache — a fixed path, because the
# path is part of the cache's key and a directory that moves never hits.
# Programs that took under a second to compile are not worth the disk.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

__version__ = "0.1.0"
