"""StateStore: persists State, historical validator sets, consensus
params, and FinalizeBlock responses (reference state/store.go).

Space optimization mirrored from the reference (store.go:818-918):
validator sets are stored in full only when they change or at
checkpoint heights; otherwise a stub records `last_height_changed` and
loads chase the pointer.

Key layout (fixed-width big-endian heights, ordered for range prunes):
  b"stateKey"            -> State proto
  b"V:" + be64(h)        -> ValidatorsInfo {last_height_changed, set?}
  b"CP:" + be64(h)       -> ConsensusParamsInfo {last_height_changed, params?}
  b"FB:" + be64(h)       -> FinalizeBlockResponse (opaque proto bytes)
"""

from __future__ import annotations

from collections import OrderedDict

from ..libs import lockrank
from ..libs import protowire as pw
from ..libs import trace
from ..store.kv import KVStore, be64
from ..types.params import ConsensusParams
from ..types.validator_set import ValidatorSet
from .state import State

VALSET_CHECKPOINT_INTERVAL = 100_000  # state/store.go valSetCheckpointInterval

# load_validators keeps the decoded sets of the records it was last
# asked for: this many full records, by recency of use, and for each this
# many resume points (the set as it stood at a height already answered)
_KEPT_RECORDS = 4
_KEPT_RESUME_POINTS = 4

_K_STATE = b"stateKey"


def _k_vals(h: int) -> bytes:
    return b"V:" + be64(h)


def _k_params(h: int) -> bytes:
    return b"CP:" + be64(h)


def _k_fbresp(h: int) -> bytes:
    return b"FB:" + be64(h)


def _info_bytes(last_height_changed: int, payload: bytes | None) -> bytes:
    w = pw.Writer().int_field(1, last_height_changed)
    if payload is not None:
        w.message_field(2, payload)
    return w.bytes()


def _info_parse(raw: bytes) -> tuple[int, bytes | None]:
    r = pw.Reader(raw)
    lhc, payload = 0, None
    while not r.at_end():
        f, w = r.read_tag()
        if f == 1 and w == pw.VARINT:
            lhc = r.read_int()
        elif f == 2 and w == pw.BYTES:
            payload = r.read_bytes()
        else:
            r.skip(w)
    return lhc, payload


class _DecodedRecord:
    """A full validators record as load_validators last decoded it:
    `raw` the stored bytes it was decoded from (an answer is served from
    here only while the store still holds exactly these), `vals` the set
    as stored, `points` {height: the set caught up to that height},
    least recently used first."""

    __slots__ = ("raw", "vals", "points")

    def __init__(self, raw: bytes, vals: ValidatorSet):
        self.raw = raw
        self.vals = vals
        self.points: OrderedDict[int, ValidatorSet] = OrderedDict()


class StateStore:
    def __init__(self, db: KVStore):
        self._db = db
        self._mtx = lockrank.RankedRLock("state.store")
        # {height of a full record: _DecodedRecord}, least recently used
        # first; under _mtx
        self._decoded: OrderedDict[int, _DecodedRecord] = OrderedDict()
        # StateMetrics once a node has instrumentation (node/node.py)
        self.metrics = None

    # -- State -------------------------------------------------------------

    def load(self) -> State | None:
        raw = self._db.get(_K_STATE)
        return State.from_proto(raw) if raw is not None else None

    def save(self, state: State) -> None:
        """SaveState: state + next/current validator info + params info in
        ONE atomic batch (state/store.go:249-294 uses a single db batch so
        a crash can never leave the state record and the validator history
        out of sync)."""
        with self._mtx:
            sets: list[tuple[bytes, bytes]] = []
            next_height = state.last_block_height + 1
            if next_height == 1:
                next_height = state.initial_height
                # genesis bootstrap: record validators for the initial height
                self._validators_entry(
                    sets, next_height, next_height, state.validators)
            self._validators_entry(
                sets, next_height + 1, state.last_height_validators_changed,
                state.next_validators)
            self._params_entry(
                sets, next_height, state.last_height_consensus_params_changed,
                state.consensus_params)
            sets.append((_K_STATE, state.to_proto()))
            self._db.write_batch(sets)

    def bootstrap(self, state: State) -> None:
        """node.BootstrapState analog: seed a store from a trusted state
        (statesync landing point; state/store.go:320)."""
        with self._mtx:
            sets: list[tuple[bytes, bytes]] = []
            height = state.last_block_height + 1
            if height == 1:
                height = state.initial_height
            if height > 1 and state.last_validators is not None:
                self._validators_entry(
                    sets, height - 1, height - 1, state.last_validators)
            self._validators_entry(sets, height, height, state.validators)
            self._validators_entry(
                sets, height + 1, height + 1, state.next_validators)
            self._params_entry(
                sets, height, state.last_height_consensus_params_changed,
                state.consensus_params)
            sets.append((_K_STATE, state.to_proto()))
            self._db.write_batch(sets)

    # -- validators --------------------------------------------------------

    def _validators_entry(self, sets: list, height: int,
                          last_height_changed: int,
                          vals: ValidatorSet | None) -> None:
        if vals is None:
            return
        if last_height_changed > height:
            raise ValueError("lastHeightChanged cannot be greater than "
                             "ValidatorsInfo height")
        # full set only on change or checkpoint (store.go:894-906)
        store_set = (height == last_height_changed
                     or height % VALSET_CHECKPOINT_INTERVAL == 0)
        payload = vals.to_proto() if store_set else None
        sets.append((_k_vals(height),
                     _info_bytes(last_height_changed, payload)))

    def load_validators(self, height: int) -> ValidatorSet:
        """LoadValidators with pointer chase (store.go:822-870).  The
        reference catches a pointer record's priorities up from the full
        record it points to, one round a height since the set last
        changed, at every call; here the catch-up resumes from the
        nearest height at or below `height` that was already answered.
        The caller gets a set nothing else references."""
        with trace.span("state", "load_validators") as sp:
            raw = self._db.get(_k_vals(height))
            if raw is None:
                raise KeyError(f"no validator set for height {height}")
            lhc, payload = _info_parse(raw)
            if payload is not None:
                lhc = height
            else:
                raw = self._db.get(_k_vals(lhc))
                if raw is None:
                    raise KeyError(
                        f"validators pointer at {height} -> {lhc} dangling")
                _, payload = _info_parse(raw)
                if payload is None:
                    raise KeyError(
                        f"validator checkpoint at {lhc} is itself empty")
            vals, path, rounds = self._caught_up(lhc, raw, payload, height)
            sp.note(path=path, rounds=rounds)
        m = self.metrics
        if m is not None:
            m.validators_loads.labels(path).inc()
            if rounds:
                m.validators_catchup_rounds.inc(rounds)
        return vals

    def _caught_up(self, lhc: int, raw: bytes, payload: bytes,
                   height: int) -> tuple[ValidatorSet, str, int]:
        """A private copy of the full record at `lhc` (stored bytes
        `raw`, set `payload`) caught up to `height`, how it was reached
        and the rounds that took.  The result is
        from_proto(payload).increment_proposer_priority(height - lhc),
        operation for operation: a resume point has the rescale, the
        shift and its rounds behind it, so only the further rounds run."""
        with self._mtx:
            rec = self._decoded.get(lhc)
            if rec is None or rec.raw != raw:
                # never decoded, or the store holds another record there
                # now (bootstrap, rollback, a prune and a regrowth)
                rec = self._decoded[lhc] = _DecodedRecord(
                    raw, ValidatorSet.from_proto(payload))
                if len(self._decoded) > _KEPT_RECORDS:
                    self._decoded.popitem(last=False)
            self._decoded.move_to_end(lhc)
            if height == lhc:
                return rec.vals.copy(), "stored", 0
            at = max((h for h in rec.points if h <= height), default=None)
            if at is None:
                vals = rec.vals.copy()
                vals.increment_proposer_priority(height - lhc)
                path, rounds = "restarted", height - lhc
                if len(rec.points) >= _KEPT_RESUME_POINTS:
                    rec.points.popitem(last=False)
            else:
                vals = rec.points.pop(at)
                path, rounds = "resumed", height - at
                for _ in range(rounds):
                    vals.proposer = vals._increment_proposer_priority()
            # the point moves up to `height`, and is the most recent
            rec.points[height] = vals
            return vals.copy(), path, rounds

    # -- consensus params --------------------------------------------------

    def _params_entry(self, sets: list, height: int,
                      last_height_changed: int,
                      params: ConsensusParams) -> None:
        store_params = height == last_height_changed
        payload = params.to_proto() if store_params else None
        sets.append((_k_params(height),
                     _info_bytes(last_height_changed, payload)))

    def load_consensus_params(self, height: int) -> ConsensusParams:
        raw = self._db.get(_k_params(height))
        if raw is None:
            raise KeyError(f"no consensus params for height {height}")
        lhc, payload = _info_parse(raw)
        if payload is None:
            raw2 = self._db.get(_k_params(lhc))
            if raw2 is None:
                raise KeyError(
                    f"params pointer at {height} -> {lhc} dangling")
            _, payload = _info_parse(raw2)
            if payload is None:
                raise KeyError(f"params at {lhc} is itself empty")
        return ConsensusParams.from_proto(payload)

    # -- FinalizeBlock responses -------------------------------------------

    def save_finalize_block_response(self, height: int,
                                     resp_bytes: bytes) -> None:
        self._db.set(_k_fbresp(height), resp_bytes)

    def load_finalize_block_response(self, height: int) -> bytes | None:
        return self._db.get(_k_fbresp(height))

    # -- pruning -----------------------------------------------------------

    def prune_states(self, retain_height: int) -> int:
        """Delete historical validator/params/response entries below
        retain_height, keeping any below-retain entry that a stub at or
        above retain_height still points to (reference state/store.go:446
        keepVals[valInfo.LastHeightChanged] = true)."""
        with self._mtx:
            keep: set[bytes] = set()
            # Stubs at height >= retain with lhc < retain all share the
            # same lhc (the set/params last changed there), so inspecting
            # the entry AT retain_height finds every live pointer target.
            # The lhc entry is kept even when retain_height itself is a
            # full checkpoint: loads above retain chase to lhc, not to the
            # checkpoint (reference keepVals[valInfo.LastHeightChanged]).
            for k_of in (_k_vals, _k_params):
                raw = self._db.get(k_of(retain_height))
                if raw is not None:
                    lhc, _payload = _info_parse(raw)
                    if lhc < retain_height:
                        keep.add(k_of(lhc))
            deletes: list[bytes] = []
            for prefix_key in (_k_vals, _k_params, _k_fbresp):
                for k, _ in self._db.iterate(prefix_key(0),
                                             prefix_key(retain_height)):
                    if k not in keep:
                        deletes.append(k)
            if deletes:
                self._db.write_batch([], deletes)
            return len(deletes)

    def prune_abci_responses(self, retain_height: int) -> int:
        """Delete only FinalizeBlock responses below retain_height — the
        data companion's independent knob (state/store.go pruneABCIResponses)."""
        with self._mtx:
            deletes = [k for k, _ in self._db.iterate(
                _k_fbresp(0), _k_fbresp(retain_height))]
            if deletes:
                self._db.write_batch([], deletes)
            return len(deletes)
