"""repro.dist — sharding, collectives, pipelining, and HLO accounting.

The distributed-execution layer the models / engine / launchers program
against:

  api          activation-sharding rules, perf options, ``constrain``
  sharding     parameter / optimizer / batch / decode-state PartitionSpecs
  collectives  dense + int8-compressed tree all-reduce (gradient psum)
  async_collectives  bucketed ppermute ring all-reduce with an AsyncHandle
               start/wait API — the overlapped backward scan's transport
  pipeline     pipeline-schedule subsystem: GPipe / 1F1B / interleaved-1F1B
               tick tables + the exact differentiable microbatch pipeline
               (and the engine's stage-sharded execution path)
  hlo_analysis compiled-artifact FLOPs/bytes/collective extraction (async
               pair-aware, replica-group byte attribution), overlap_fraction
               + roofline
"""
