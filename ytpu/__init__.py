"""ytpu — a TPU-native multi-tenant CRDT sync framework.

Capabilities mirror y-crdt/Yrs (see SURVEY.md): Yjs-wire-compatible shared
types (Text, Array, Map, Xml, weak links, subdocuments) with YATA conflict
resolution, state-vector delta sync, lib0 v1/v2 encodings, undo/redo,
snapshots and the y-sync/Awareness protocol — executed as a batched engine:

- `ytpu.core` / `ytpu.types` — the host semantic oracle (per-doc API).
- `ytpu.models.batch_doc` — N docs as one struct-of-arrays pytree; the
  flagship `apply_update_batch` / `encode_diff_batch` JAX programs.
- `ytpu.ops` — device kernels (state-vector math, integration waves, codecs).
- `ytpu.parallel` — the doc mesh: a server's rooms over the chips of a host.
- `ytpu.sync` — y-sync protocol + Awareness host frontends.
"""

__version__ = "0.1.0"

from ytpu.core import (  # noqa: F401
    DeleteSet,
    Doc,
    ID,
    Options,
    Snapshot,
    StateVector,
    Transaction,
    Update,
    decode_update_v1,
    diff_updates_v1,
    encode_state_vector_from_update_v1,
    merge_updates_v1,
)
from ytpu.types import (  # noqa: F401
    Array,
    ArrayPrelim,
    Map,
    MapPrelim,
    Text,
    TextPrelim,
    XmlElement,
    XmlFragment,
    XmlText,
)
