from .counter import CounterMachine
from .fifo import FifoMachine
from .fifo_client import FifoClient, Mailbox, StopSending
from .jit_fifo import JitFifoMachine
from .jit_kv import JitKvMachine, JitRecordKvMachine
from .kv import KvMachine
from .registers import RegisterMachine
from .queue import QueueMachine
from .quorum_queue import QuorumQueueMachine
from .stream import StreamLogMachine, StreamMachine
from .ttl_kv import TtlKvMachine

__all__ = ["CounterMachine", "FifoMachine", "FifoClient", "JitFifoMachine",
           "JitKvMachine", "JitRecordKvMachine", "KvMachine", "Mailbox",
           "QueueMachine", "QuorumQueueMachine",
           "RegisterMachine", "StopSending", "StreamLogMachine",
           "StreamMachine",
           "TtlKvMachine"]
