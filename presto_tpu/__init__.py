"""presto_tpu — a TPU-native distributed SQL query engine.

A ground-up rebuild of the capabilities of kaka11chen/presto (Presto
0.216-SNAPSHOT, coordinator/worker MPP SQL engine) designed for TPU hardware:
columnar pages live in HBM as JAX arrays, relational operators are XLA/Pallas
kernels, repartitioning is jax.lax.all_to_all over the ICI mesh, and the
host-side control plane reproduces the coordinator/worker semantics.

Layer map (mirrors SURVEY.md §1):
  sql/        parser, analyzer, logical planner, optimizer   (L4)
  plan/       plan nodes, fragmenter, distribution           (L4)
  expr/       row expressions traced to fused jax fns        (L7 codegen)
  page.py     columnar Page/Block device representation      (L7 data plane)
  ops/        relational kernels (filter, agg, join, sort)   (L6 operators)
  exec/       driver/pipeline runner, task execution         (L6)
  parallel/   mesh, shardings, all_to_all exchange           (L8)
  connectors/ tpch generator, memory tables                  (L9/L10)
  server/     coordinator/worker control plane               (L2/L3/L11)
"""

import jax

# SQL semantics need 64-bit ints (BIGINT, short DECIMAL) and doubles. XLA:TPU
# emulates 64-bit with int32 pairs; exactness beats the emulation cost for the
# key/decimal paths, and hot float math stays in 32-bit where the planner says
# it's safe.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache so process restarts warm-start their
# executables: at JAX_COMPILATION_CACHE_DIR when the caller sets it, else
# at <checkout>/.jax_cache (exec/qcache.py). Configured at import — before
# any compile can latch the cache uninitialized — and a pure config
# update, so no backend is touched here.
from .exec.qcache import enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache()

from . import types  # noqa: E402
from .page import Block, Page  # noqa: E402

__version__ = "0.1.0"
__all__ = ["types", "Block", "Page"]
