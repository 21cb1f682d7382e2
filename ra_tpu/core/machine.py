"""User state-machine behaviour — the ra_machine equivalent.

Mirrors the callback contract of /root/reference/src/ra_machine.erl:233-287:
mandatory ``init/1`` + ``apply/3``; optional ``state_enter/2``, ``tick/2``,
``snapshot_installed/4``, aux handlers, ``overview/1`` and versioning
(``version/0`` + ``which_module/1``).

Two flavours exist:

* :class:`Machine` — the classic host-side behaviour.  ``apply`` runs in
  Python on the host, may return arbitrary effects, and state may be any
  Python object.  This is always available and is the default.
* :class:`JitMachine` — the TPU-native variant (the ``ra_machine_xla`` of the
  north star).  Its ``apply`` must be a pure, shape-stable JAX function
  ``(meta_array, cmd_array, state_pytree) -> (state_pytree, reply_array)``
  so committed batches can be folded on-device by the lane engine — via
  ``lax.scan`` or the one-shot ``jit_apply_batch`` window fold (see
  ra_tpu/engine/lockstep.py, step 5).  The window fold must preserve
  command ORDER; commutative machines fold trivially, and order-dependent
  ones may fold vectorized when the algebra allows (see jit_fifo/jit_kv).
  A JitMachine also provides the host-side protocol so the same machine
  works on both paths.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

from .types import Effects


def cond_concrete(pred, true_fn, false_fn, operands):
    """``lax.cond`` that short-circuits in Python when ``pred`` is
    concrete (host/eager calls): picks the branch without tracing the
    other, avoiding lax.cond's per-call branch retrace outside jit.
    Under tracing it is exactly ``lax.cond``.

    Concreteness is probed by ``bool(pred)`` rather than an
    ``isinstance(pred, jax.core.Tracer)`` check: ``jax.core.Tracer`` is
    a deprecated public alias slated for removal, while a tracer
    refusing bool() (TracerBoolConversionError) is the stable,
    documented contract."""
    import jax
    from jax import lax

    try:
        concrete = bool(pred)  # ra13-ok: the sanctioned concreteness probe — TracerBoolConversionError is caught and routes traced preds to lax.cond
    except jax.errors.TracerBoolConversionError:
        return lax.cond(pred, true_fn, false_fn, operands)
    return true_fn(operands) if concrete else false_fn(operands)


@dataclass(frozen=True, slots=True)
class ApplyMeta:
    """Metadata passed to apply/3 (ra_machine:command_meta_data()).
    Slotted: one instance per applied command on every member — the
    apply fold is the classic plane's hottest loop (ISSUE 13)."""

    index: int
    term: int
    system_time: float = 0.0
    machine_version: int = 0
    from_: Any = None
    reply_mode: Any = None


class Machine:
    """Base class for host-side state machines.

    Subclasses must override :meth:`init` and :meth:`apply`.  All other
    callbacks have no-op defaults matching the optional-callback semantics of
    the reference (ra_machine.erl:211-221).
    """

    #: bump when apply semantics change; see version gating in the core
    #: (ra_server.erl:2671-2732)
    version: int = 0

    def init(self, config: dict) -> Any:
        raise NotImplementedError

    def apply(self, meta: ApplyMeta, command: Any, state: Any):
        """Apply a committed user command.

        Returns ``(new_state, reply)`` or ``(new_state, reply, effects)``.
        """
        raise NotImplementedError

    #: OPTIONAL batched apply (ISSUE 13): when a machine sets this to a
    #: callable ``apply_batch(meta, commands, state) -> (state, replies)``
    #: (or ``(state, replies, effects)``), the core's apply fold hands it
    #: RUNS of contiguous same-term plain user commands in one call
    #: instead of one :meth:`apply` per entry.  ``meta`` describes the
    #: FIRST entry of the run; command ``i`` applied at ``meta.index + i``
    #: (machines that key on the index compute it that way).  ``replies``
    #: must be one reply per command, in order — they feed the same
    #: notify/await-consensus plumbing the per-entry path feeds.  The
    #: contract is exact order equivalence with folding :meth:`apply`
    #: over the run; machines whose apply has per-command effects should
    #: leave this None (the default) and take the per-entry path.
    apply_batch = None

    # -- optional callbacks -------------------------------------------------

    def state_enter(self, raft_state: str, state: Any) -> Effects:
        return []

    def tick(self, time_ms: float, state: Any) -> Effects:
        return []

    def snapshot_installed(self, meta, state, old_meta, old_state) -> Effects:
        return []

    def init_aux(self, name: str) -> Any:
        return None

    def handle_aux(self, raft_state: str, msg_type: str, msg: Any,
                   aux_state: Any, internal) -> tuple:
        """Returns (aux_state, effects)."""
        return aux_state, []

    def overview(self, state: Any) -> Any:
        return state

    def which_module(self, version: int) -> "Machine":
        """Machine-version dispatch (ra_machine.erl:346-362).  Return the
        machine implementing ``version``; default: self for all versions."""
        return self

    def snapshot_module(self):
        """Override to customise the snapshot format (ra_machine.erl:435)."""
        return None

    def live_indexes(self, state: Any) -> list:
        return []


class SimpleMachine(Machine):
    """Wraps a plain ``fun(command, state) -> state`` as a machine — the
    ``{simple, Fun, Init}`` config variant (ra_machine_simple.erl, selected in
    ra_server.erl:277-283).  Replies are the new state."""

    def __init__(self, fn: Callable[[Any, Any], Any], initial_state: Any):
        self._fn = fn
        self._initial = initial_state

    def init(self, config: dict) -> Any:
        return self._initial

    def apply(self, meta: ApplyMeta, command: Any, state: Any):
        new_state = self._fn(command, state)
        return new_state, new_state


#: compiled host-path apply fns shared across same-config instances
_HOST_APPLY_JIT_CACHE: dict = {}


class JitMachine(Machine):
    """TPU-native machine: committed commands are dense arrays folded
    on-device.

    Contract (enforced by the lane engine, not here):

    * ``state`` is a JAX pytree of fixed-shape arrays (one leading lane axis
      when used under the batched engine).
    * :meth:`jit_apply` is pure and traceable: it is called under ``jit`` /
      ``vmap`` / ``lax.scan`` and must not use data-dependent Python control
      flow.
    * :meth:`encode_command` / :meth:`decode_reply` convert between host
      commands and the dense on-device representation.
    """

    #: shape/dtype spec of one encoded command, e.g. ("int32", (2,))
    command_spec: tuple = ("int32", ())
    #: shape/dtype spec of one reply
    reply_spec: tuple = ("int32", ())

    #: OPTIONAL vectorized read path (ISSUE 20): shape/dtype spec of one
    #: encoded query, or None when the machine has no jittable query
    #: kernel (the engine's lease/read-index plane then refuses reads
    #: for it).  Unlike commands, queries NEVER mutate state and never
    #: enter the log — the lane engine evaluates them against the
    #: leader replica once lease/read-index authority certifies the
    #: watermark (the consistent_query contract, ra_server.erl:3032+,
    #: with zero log appends).
    query_spec: Optional[tuple] = None
    #: shape/dtype spec of one query reply
    query_reply_spec: tuple = ("int32", ())

    #: set True when jit_apply_batch folds a whole committed window in
    #: one shot FASTER than the engine's representative lax.scan.  The
    #: fold must be IN ORDER-equivalent to applying the masked commands
    #: sequentially — commutativity is sufficient but not necessary
    #: (jit_fifo/jit_kv fold order-dependent vocabularies vectorized,
    #: falling back to sequential_window_fold for the hard windows)
    supports_batch_apply: bool = False

    def jit_init(self, n_lanes: int) -> Any:
        """Return the initial state pytree with a leading lane axis."""
        raise NotImplementedError

    def jit_apply(self, meta, command, state):
        """Pure JAX apply: (meta arrays, encoded cmd, state) -> (state, reply)."""
        raise NotImplementedError

    def jit_query(self, queries, state):
        """Pure vectorized read kernel (ISSUE 20): evaluate a window of
        encoded queries against ONE replica's machine state.

        ``queries``: [..., Kr, Cq] with Cq from :attr:`query_spec` and
        arbitrary leading (lane) dims; ``state``: the machine pytree
        with the SAME leading dims (the engine hands it every member's
        replica, ``[lanes, members]`` leading, and keeps the leader's
        answers: it never copies a replica to pick one).  Returns replies
        [..., Kr, Wq] per :attr:`query_reply_spec`.  Must be pure and
        traceable (called inside the jitted step) and must NOT mutate
        state — reads never enter the log.  Only called when
        :attr:`query_spec` is not None."""
        raise NotImplementedError

    def encode_query(self, query: Any):
        """Host query -> encoded int row (the read twin of
        :meth:`encode_command`)."""
        raise NotImplementedError

    def decode_query_reply(self, reply_array) -> Any:
        return reply_array

    def jit_apply_batch(self, meta, commands, mask, state):
        """Fold a window of commands at once, order-equivalently to a
        sequential masked jit_apply fold.  commands: [..., A, C];
        mask: bool[..., A] (True = apply); state leading dims match the
        ... prefix.  Returns the new state (per-command replies are not
        part of this path — the engine discards them).  Only called when
        supports_batch_apply is True.  The default is the sequential
        fold; machines override it to add vectorized fast paths.

        The engine calls it with leading dims ``(N,)`` or ``(N, P)``,
        so write the fold for any: ``(N,)`` is one replica a lane, the
        lane's representative, whose result the engine hands to the
        members that share its apply interval, wherever the machine's
        state is no larger in bytes than the ring; ``(N, P)`` is every
        replica under its own mask, in place, for a state larger than
        the ring and in any round in which some member does not share
        (``lockstep._lane_fold_fits``, stage 5 of ``_step``).
        ``meta["index"]`` has the mask's shape and ``meta["term"]``
        broadcasts to it."""
        return self.sequential_window_fold(meta, commands, mask, state)

    def jit_fallback(self, commands, mask):
        """Whether :meth:`jit_apply_batch` folds this window (the same
        ``commands`` and ``mask``) by the in-order sequential fold: a
        bool scalar, or None where the fold has no sequential branch.
        The engine counts it (``apply_fallback_rounds``).  The default
        fold is the sequential one; a machine whose fold gates a
        vectorized path (``window_fold_dispatch``) returns "some masked
        command is sequential-only"."""
        if type(self).jit_apply_batch is JitMachine.jit_apply_batch:
            return True
        return None

    def window_fold_dispatch(self, meta, commands, mask, state):
        """Shared jit_apply_batch dispatcher for machines with a
        vectorized common-case fold: route to ``self._batch_fast``
        unless :meth:`jit_fallback` says the window needs the in-order
        sequential fold.  Concrete predicates branch in Python
        (host/eager callers); traced ones become a single lax.cond."""
        import jax.numpy as jnp
        return cond_concrete(
            jnp.logical_not(self.jit_fallback(commands, mask)),
            lambda args: self._batch_fast(*args),
            lambda args: self.sequential_window_fold(meta, *args),
            (commands, mask, state))

    def sequential_window_fold(self, meta, commands, mask, state):
        """Masked in-order lax.scan of jit_apply over the window axis —
        the universal (slow) jit_apply_batch; custom folds use it as
        their fallback branch for windows they cannot vectorize."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        idx = meta["index"]
        # term arrives window-shaped (the engine passes [N,1] or
        # [N,1,1], as the mask has a member axis or not); give
        # jit_apply the same per-command leading dims as index so a
        # machine reading meta["term"] broadcasts correctly
        term = jnp.broadcast_to(meta["term"], idx.shape)

        def body(mac, xs):
            cmd, do, ix, tm = xs
            new, _reply = self.jit_apply(
                {"index": ix, "term": tm}, cmd, mac)
            merged = jax.tree.map(
                lambda n, o: jnp.where(
                    do.reshape(do.shape + (1,) * (n.ndim - do.ndim)), n, o),
                new, mac)
            return merged, None

        xs = (jnp.moveaxis(commands, -2, 0), jnp.moveaxis(mask, -1, 0),
              jnp.moveaxis(idx, -1, 0), jnp.moveaxis(term, -1, 0))
        final, _ = lax.scan(body, state, xs)
        return final

    #: the names of what ``jit_counts`` counts, and the ``overview()``
    #: section the engine puts their sums over lanes in (None: none)
    counts_name: Optional[str] = None
    counts_keys: tuple = ()

    def jit_counts(self, state):
        """int32[..., len(counts_keys)]: the machine's own counts of one
        replica (a machine with ``counts_keys`` only).  The engine sums
        the leaders' over lanes every round, as a step aux."""
        raise NotImplementedError

    def encode_command(self, command: Any):
        raise NotImplementedError

    def decode_reply(self, reply_array) -> Any:
        return reply_array

    # -- host-side protocol so JitMachines also run on the classic path ----

    def init(self, config: dict) -> Any:
        import numpy as np  # local import: host path only
        import jax
        state = self.jit_init(1)
        return jax.tree.map(lambda x: np.asarray(x)[0], state)

    def apply(self, meta: ApplyMeta, command: Any, state: Any):
        import jax.numpy as jnp
        import jax
        # jit once per (class, scalar config): an eager jit_apply
        # re-traces control-flow primitives (lax.fori_loop bodies) on
        # every call, turning each classic-path apply into a fresh
        # compile — and caching per-instance would still compile once
        # per cluster member.  Sound because jit_apply is pure in
        # (meta, command, state) given the config (the class contract
        # above) — but only when the whole config is scalar: a machine
        # holding non-scalar config (arrays, tuples) falls back to a
        # per-instance compile, since two such instances could share
        # every scalar attr yet differ in behavior.
        attrs = [(k, v) for k, v in sorted(self.__dict__.items())
                 if not k.startswith("_")]
        if all(isinstance(v, (int, float, str, bool)) for _k, v in attrs):
            key = (type(self), tuple(attrs))
            fn = _HOST_APPLY_JIT_CACHE.get(key)
        else:
            # non-scalar config: keep the compile on the instance itself
            # (an id()-keyed shared cache could alias a GC'd instance)
            key = None
            fn = self.__dict__.get("_host_apply_jit")
        if fn is None:
            bound = type(self).jit_apply
            inst = self
            fn = jax.jit(lambda m, c, s: bound(inst, m, c, s))
            if key is not None:
                _HOST_APPLY_JIT_CACHE[key] = fn
            else:
                self.__dict__["_host_apply_jit"] = fn
        meta_arr = {"index": jnp.int32(meta.index), "term": jnp.int32(meta.term)}
        enc = self.encode_command(command)
        new_state, reply = fn(meta_arr, enc, state)
        return new_state, self.decode_reply(reply)
