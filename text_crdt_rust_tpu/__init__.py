"""text_crdt_rust_tpu — a TPU-native list/text CRDT framework.

Brand-new rebuild of `josephg/text-crdt-rust` (see SURVEY.md): Yjs/YATA
integration semantics over an automerge-style (agent, seq) data model.

Layout (see each subpackage's __init__ for what is implemented):

- ``models/``    document engines: Python oracle, C++ native engine
                 (ctypes), peer sync;
- ``ops/``       device kernels: the RLE run engines (``rle`` /
                 ``rle_hbm`` / ``rle_lanes`` / ``rle_mixed``), the
                 per-char ``flat`` engine, the op compiler (``batch``);
- ``parallel/``  mesh sharding (dp/sp) + the causal buffer;
- ``utils/``     RLE span algebra, trace loader, metrics, checkpoint;
- ``native/``    C++ sources + build;
- ``examples/``  soak and stats CLIs;
- ``config``     the dataclass config layer.
"""

from .common import (
    CLIENT_INVALID,
    CRDT_DOC_ROOT,
    CRDTLocation,
    LocalOp,
    ROOT_ORDER,
    ROOT_REMOTE_ID,
    RemoteDel,
    RemoteId,
    RemoteIns,
    RemoteTxn,
)

__version__ = "0.1.0"

__all__ = [
    "CLIENT_INVALID",
    "CRDT_DOC_ROOT",
    "CRDTLocation",
    "LocalOp",
    "ROOT_ORDER",
    "ROOT_REMOTE_ID",
    "RemoteDel",
    "RemoteId",
    "RemoteIns",
    "RemoteTxn",
    "__version__",
]
