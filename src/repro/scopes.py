"""The names the program's layers carry in a profiler trace.

Device scopes (``jax.named_scope``) are set inside jitted code only:
they go into each operation's HLO metadata, and a TPU trace carries them
in the op-name path (``tf_op``) of every device op, e.g.
``jit(_stacked_epoch)/while/body/.../conv2d/concatenate``. A program
that runs eagerly is its own jit (``jit(cholesky)``) and carries no
scope.

Host spans (``jax.profiler.TraceAnnotation``) are opened on the host,
never inside a traced function, and land in the profiler's own trace on
the device ops' clock. Both cost next to nothing while no profiler runs;
``jax.profiler.trace`` is the only switch.
"""

# device scopes
CONV2D = "conv2d"            # the lane-dense conv kernel, its dX and dW
ELM_STATS = "elm_stats"      # U = HᵀH, V = HᵀT
BETA_SOLVE = "beta_solve"    # Cholesky and both triangular solves
SGD_UPDATE = "sgd_update"    # the ELM loss gradient and the SGD step
READOUT = "readout"          # Hβ, the ELM readout of a prediction
REDUCE = "reduce"            # member averages, syncs, gossip mixing
EPOCH_GATHER = "epoch_gather"   # an epoch's batches gathered on the device
STEP_RECORD = "step_record"     # members' params written at each SGD step
SCOPES = (CONV2D, ELM_STATS, BETA_SOLVE, SGD_UPDATE, READOUT, REDUCE,
          EPOCH_GATHER, STEP_RECORD)

# host spans of the stacked Map phase (caller's thread)
MAP_EPOCH_BUILD = "repro.map.epoch_build"   # host part of an epoch build
MAP_PUT = "repro.map.put"                   # host-to-device of partitions
                                            # or of a chunk
MAP_DISPATCH = "repro.map.dispatch"         # the epoch-chunk program call
MAP_GATHER = "repro.map.gather"             # the host waits on the stats
MAP_REDUCE = "repro.reduce"                 # averaged model and syncs

# host spans of the serving worker
SERVE_COLLECT = "repro.serve.collect"   # first request of a batch to flush
SERVE_FLUSH = "repro.serve.flush"       # args n, bucket, wait_us
SERVE_SCORE = "repro.serve.score"       # BucketedScorer.score_block
SERVE_DISPATCH = "repro.serve.dispatch"   # pad, host-to-device, the call
SERVE_FETCH = "repro.serve.fetch"       # wait on the device, copy back
SPANS = (MAP_EPOCH_BUILD, MAP_PUT, MAP_DISPATCH, MAP_GATHER, MAP_REDUCE,
         SERVE_COLLECT, SERVE_FLUSH, SERVE_SCORE, SERVE_DISPATCH,
         SERVE_FETCH)
