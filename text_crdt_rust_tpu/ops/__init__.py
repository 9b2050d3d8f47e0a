"""JAX/XLA device kernels — the TPU-native document engines.

All engines share one semantic model (the flattened YjsSpan item layout,
see ``span_arrays``) and cross-check bit-identically in ``tests/``:

- ``flat``      — correctness-first engine: per-item arrays in document
                  order, every op O(capacity) fully-vectorized. Complete
                  op surface (local edits, remote inserts with the YATA
                  integrate scan + name-rank tiebreak, remote delete
                  tombstoning). The device twin of ``models.oracle``.
- ``rle``       — the north-star engine (round 3): state is RLE RUNS
                  (``(start_order, signed_len)`` rows — `span.rs:6-119`'s
                  compression on device), blocked with a logical block
                  order and leaf SPLITS instead of global rebalances
                  (`mutations.rs:623-808`). Consumes the RLE-merged op
                  stream (``batch.merge_patches``). VMEM-resident.
- ``rle_hbm``   — same run algebra with HBM state planes behind a
                  one-block VMEM window: millions of run rows (the kevin
                  prepend worst case, >VMEM documents).
- ``rle_lanes`` — per-lane DIVERGENT documents: B distinct streams, one
                  op per lane per step, warm-startable across compiled
                  chunks (the streaming config-5 engine).
- ``rle_mixed`` — the round-4 unification: the FULL op surface (local +
                  remote YATA integrate + remote delete, `doc.rs:242-348`)
                  on the run representation — runs the config-4 storm on
                  state that is runs, not chars.
- ``rle_lanes_mixed`` — the round-5 unification: the full op surface on
                  PER-LANE DIVERGENT documents (each lane its own remote
                  stream — the production sync shape; config 5's remote
                  variant), with per-lane by-order origin tables and a
                  lane-vectorized YATA scan.
- ``blocked`` / ``blocked_hbm`` — the round-2 per-character block
                  engines (kept as references and for the unmerged-stream
                  path); ``blocked_mixed`` adds the remote-op hot path
                  in-kernel on char rows (superseded by ``rle_mixed``).

``batch`` compiles editing traces into fixed-shape op tensors (the
host-side analog of the reference's bench replay loop,
`benches/yjs.rs:32-49`), RLE-merges patch streams, and owns the agent
name-rank table incl. cross-epoch onboarding (``rank_remap``).

``stream_scan`` is the >HBM read path: host-resident run planes of any
length, scanned tile-by-tile with host-carried prefixes (SURVEY §5's
"block-wise scans for >HBM documents"; mutation at that scale goes
through ``rle_hbm`` or ``parallel.sp_apply``).
"""
