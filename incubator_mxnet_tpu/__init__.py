"""incubator_mxnet_tpu — a TPU-native deep-learning framework.

A ground-up re-design of Apache MXNet's capabilities (reference:
seppo0010/incubator-mxnet) for TPU hardware: JAX/XLA/Pallas compute, SPMD
parallelism over jax.sharding meshes, functional autodiff under an
imperative (Gluon-style) and symbolic (Module-style) API.

Usage mirrors MXNet::

    import incubator_mxnet_tpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu(0))
    with mx.autograd.record():
        y = (x * 2).sum()
    y.backward()
"""
__version__ = "0.1.0"

import time as _time

_T_IMPORT = _time.perf_counter()     # mxtpu_import_seconds, set at the end

# Multi-process (DCN) workers: jax.distributed must come up BEFORE anything
# touches the XLA backend, and importing this package initialises it (device
# queries in context/ndarray). tools/launch.py sets this env per worker.
# (config only touches os — safe this early.)
from . import config as _config

if _config.get_env("MXTPU_NUM_PROC") > 1 and \
        _config.get_env("MXTPU_COORD_ADDR"):
    import jax as _jax
    from .base import distributed_is_initialized as _dist_up
    if not _dist_up():  # user may have done it already
        _jax.distributed.initialize(_config.get_env("MXTPU_COORD_ADDR"),
                                    _config.get_env("MXTPU_NUM_PROC"),
                                    _config.get_env("MXTPU_PROC_ID"))

_config.place_compile_cache()

if _config.get_env("MXTPU_MATMUL_PRECISION"):
    import jax as _jax
    _jax.config.update("jax_default_matmul_precision",
                       _config.get_env("MXTPU_MATMUL_PRECISION"))

# telemetry depends only on config/stdlib — import it before the
# subsystems that instrument against it, and honor the autoflush knob
from . import telemetry
telemetry._maybe_autostart()

from . import base
from .base import MXNetError
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, num_gpus, num_tpus
from . import ndarray
from . import ndarray as nd
from . import autograd
from .ndarray import random as random
from . import initializer
from . import initializer as init
from . import optimizer
from .optimizer import Optimizer
from . import metric
from . import lr_scheduler
from . import callback
from . import kvstore
from . import kvstore as kv
from . import gluon
from . import jit
from . import parallel
from . import recordio
from . import io
from . import model
from .model import save_checkpoint, load_checkpoint, FeedForward
from . import symbol
from . import symbol as sym
from .executor import Executor
from . import module
from . import module as mod
from . import rnn
from . import models
from . import ops
from . import profiler
from . import monitor
from .monitor import Monitor
from . import operator
from . import subgraph
from . import config
from . import error
from . import registry
from . import engine
from . import runtime
from . import util
from .util import is_np_array, set_np, reset_np, np_shape, np_array
from . import image
from . import rtc
from . import library
from . import attribute, name
from .attribute import AttrScope
from .name import NameManager
from . import visualization
from . import visualization as viz
from . import test_utils
from . import numpy
from . import numpy as np
from . import numpy_extension
from . import numpy_extension as npx
from . import contrib
from . import serving

# ---- env-driven startup behaviors (config.ENV_VARS documents each) ----
if config.get_env("MXTPU_SEED") is not None:
    random.seed(config.get_env("MXTPU_SEED"))

if config.get_env("MXTPU_PROFILER_AUTOSTART"):
    # MXNET_PROFILER_AUTOSTART analog: record from import, dump at exit
    import atexit as _atexit

    profiler.set_config(filename=config.get_env("MXTPU_PROFILER_FILENAME"))
    profiler.set_state("run")
    _atexit.register(profiler.dump)

# this import, as the program times it: the first device touch (the global
# PRNG key of ndarray/random.py starts the backend and runs a program) and
# everything else
telemetry.setup_phases.record_import(_time.perf_counter() - _T_IMPORT,
                                     random.BACKEND_TOUCH_S)
