from .engine import (  # noqa: F401
    ConsensusService,
    Session,
    Ticket,
    session_hash,
)
from .kv import (  # noqa: F401
    OP_CAS,
    OP_DELETE,
    OP_GET,
    OP_PUT,
    GroupReplica,
    KvCodecError,
    KvOp,
    KVSession,
    ReplicatedKV,
    decode_op,
    encode_op,
)
