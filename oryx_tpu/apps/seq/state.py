"""In-memory seq model state shared by the speed and serving tiers.

Item embeddings live in the SAME FactorStore the ALS tiers use
(apps/als/state.py): a growing arena whose device copy resyncs by
dirty-row delta (PR 3's scatter_rows machinery), so the speed tier's
per-item UP writes reach the serving matrix as row scatters, never a
re-upload. The encoder's own weights ride the MODEL message and swap
atomically with the announced item-id set. WHICH encoder is written in the
artifact (extension "encoder", absent: "gru"; ops/seq.py `encoder_for`):
the GRU's small recurrent tensors (Wx/Wh/b), or an SDAR block's layers and
its input embedding `E_in`, row-aligned with the announced ids.
"""

from __future__ import annotations

import threading

import numpy as np

from oryx_tpu.apps.als.state import FactorStore
from oryx_tpu.apps.updates import parse_update_message
from oryx_tpu.ops.seq import GruEncoder, encoder_for


class SeqState:
    """Embeddings + the encoder and its weights + expected-id readiness
    bookkeeping."""

    def __init__(self, dim: int, window: int, encoder=None):
        self.dim = dim
        self.window = window
        self.encoder = encoder or GruEncoder(dim, window)
        self.items = FactorStore(dim)
        self.params: dict | None = None
        # announced id -> its row of the encoder's input embedding, for an
        # encoder that has one apart from the catalog (SDAR's E_in). An
        # item that arrives by UP after the model is not in it: it has a
        # head row (it can be recommended) and no input embedding (it is
        # skipped as context) until the next generation
        self.token_of: dict[str, int] = {}
        self.expected_items: set[str] | None = None
        self._have = 0
        self._frac_lock = threading.Lock()

    # -- writes (keep the readiness counter true) --------------------------

    def set_item(self, ident: str, vector: np.ndarray) -> None:
        present_before = ident in self.items
        self.items.set(ident, vector)
        if self.expected_items is not None:
            with self._frac_lock:
                if ident not in self.expected_items:
                    self.expected_items.add(ident)
                    self._have += 1
                elif not present_before:
                    self._have += 1

    def recount(self) -> None:
        with self._frac_lock:
            ex = self.expected_items
            self._have = len(ex & set(self.items.ids())) if ex is not None else 0

    def set_expected(self, item_ids) -> None:
        self.expected_items = set(item_ids)
        self.recount()

    def fraction_loaded(self) -> float:
        if self.expected_items is None or self.params is None:
            return 0.0
        total = len(self.expected_items)
        if total == 0:
            return 1.0
        with self._frac_lock:
            return self._have / total


def apply_seq_update(
    state: SeqState | None, key: str | None, message: str
) -> SeqState | None:
    """Apply one update-topic message — the single implementation behind
    both the speed and serving managers (the ALS apply_update_message
    pattern):

    MODEL / MODEL-REF -> a fresh state when the embedding width or the
    context window changed, else retain only the announced item ids;
    recurrent weights (inline tensors) swap in either way. The embedding
    matrix itself arrives as the UP row flood that follows (ALS's
    EnqueueFeatureVecsFn streaming pattern), or inline as an "E" tensor
    when the publisher chose to ship it whole.
    UP ["E", id, vec] -> set one item row (width-mismatched stale
    updates from an older-rank model are dropped).
    """
    from oryx_tpu.common.artifact import read_artifact_from_update

    if key in ("MODEL", "MODEL-REF"):
        art = read_artifact_from_update(key, message)
        return adopt_model(
            state, art.get_extension, art.tensors or {},
            art.get_extension_list("ItemIDs"), art,
        )
    elif key == "UP":
        if state is None:
            return None  # updates before any model: nothing to apply to
        kind, ident, vec, _known = parse_update_message(message)
        if kind != "E" or len(vec) != state.dim:
            return state
        state.set_item(ident, vec)
    return state


def adopt_model(state: SeqState | None, ext, tensors: dict, item_ids: list, art=None) -> SeqState:
    """Swap in a generation: `ext(key, default)` reads the artifact's
    extensions, `tensors` are its tensors (host arrays off the update topic,
    or arrays already on the device from a loader that made them there).
    The extension "encoder" names the encoder; its tensors are checked
    against the shapes its other extensions state."""
    encoder = encoder_for(str(ext("encoder", "gru")), ext)
    params = encoder.load_params(tensors)  # checks names and shapes
    if encoder.own_input and not item_ids:
        raise ValueError(f"a {encoder.name} MODEL message has to announce its ItemIDs")
    if state is None or state.dim != encoder.dim or state.encoder.name != encoder.name:
        state = SeqState(encoder.dim, encoder.window, encoder)
    else:
        state.window = encoder.window
        state.encoder = encoder
    state.params = params
    state.token_of = {ident: t for t, ident in enumerate(item_ids)} if encoder.own_input else {}
    if item_ids:
        state.set_expected(item_ids)
        state.items.retain(set(item_ids))
        state.recount()
    else:
        state.set_expected(state.items.ids())
    if art is not None:
        from oryx_tpu.apps.als.state import _adopt_quality_profile

        _adopt_quality_profile(art, item_ids)
    e = tensors.get("E")
    if e is not None and item_ids and len(e) == len(item_ids):
        state.items.bulk_set(item_ids, np.asarray(e, dtype=np.float32))
        state.recount()
    return state
