"""Block-paged KV cache pool for continuous batching (see docs/serving.md).

The dense ``ServeEngine`` path allocates one ``(B, Smax, ...)`` cache tree
per generation, so a new request can only start when a whole generation
ends.  This module stores the caches of *all* in-flight requests in one
device-resident pool of fixed-size pages and lets requests join and leave
the running batch between decode steps — the admission path the paper's
HEFT_RT scheduler needs to pay off on dynamic arrivals.

Layout
------
Per paged cache leaf (names ``k``/``v``/``ckv``/``kr`` — the same name-based
classification ``dist.sharding._cache_rule`` uses), the dense leaf's batch
axis becomes ``num_pages + 1`` and its ``Smax`` axis becomes ``page_size``:

    dense  (B, Smax, KV, hd)   →  pool (num_pages + 1, page_size, KV, hd)

The final page (index ``num_pages``) is the *scratch page*: padded batch
lanes and unreserved page-table tail entries point at it, so every tick runs
with fully static shapes and stray writes land somewhere harmless.  State
leaves (``conv``/``ssm`` — no sequence axis) live in a parallel *state pool*
with ``max_batch + 1`` slots, the last being the scratch state slot.  Leaves
stacked under ``stages`` keep their leading ``num_stages`` axis.  Pool
leaves therefore have the same rank as their dense counterparts, which is
why ``dist.sharding.page_pspecs`` can reuse the cache sharding rule
structurally (page dim replicated like batch, ``page_size`` like ``Smax``).

A per-slot page table (``max_batch + 1`` rows × ``pages_per_slot`` int32
page ids; row ``max_batch`` is all-scratch) maps each sequence onto its
pages.  All pages a request will ever need are reserved at admission
(``ceil((S0 + new_tokens) / page_size)``), so decode can never run out of
pages mid-flight: exhaustion only gates *admission*, and callers queue —
never drop — rejected requests.

Decode tick
-----------
Each tick runs the standard ``decode_step`` with a per-row position vector
and writes back only the newly written token to its page.  A plain
attention layer (:func:`in_place_layers`) attends in the pool: the layer
scan takes the pool as a loop-invariant operand and
``models.attention.paged_decode_attention`` reads each lane's live pages
(in place on a TPU), so no dense cache exists.  Every other layer gathers
the active slots' pages into a dense-shaped ``(B, Smax, ...)`` view.  Rows
are independent in every einsum/softmax of the model, stale garbage beyond
a row's position is masked to ``-inf`` before softmax (pool values are
always finite), and RoPE sees the same per-row positions — so off the TPU
each request's tokens are **bit-identical** to the dense single-request
oracle (``ServeEngine.generate``), under any admission interleaving; the
TPU kernel matches it to f32 rounding.  The active-lane count is padded to
a power-of-two bucket (same idiom as ``MappingFabric``;
``sched_integration.fabric.pow2_bucket``), so joins and leaves retrace at
most ``log2(max_batch) + 1`` decode variants.

Pages are also the migration and recovery unit: :meth:`PagedRuntime
.snapshot_slot` captures one request's page set (plus its host-side decode
state) as numpy, and :meth:`PagedRuntime.restore_slot` re-admits it on any
engine with free capacity — the continuous-batching analogue of the chaos
tier's whole-cache snapshot/restore.
"""

from __future__ import annotations

import math
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import tree_map_with_path

from repro.kernels.fused_decision import decision_ref, pack_tick_outputs
from repro.obs.device import accumulate_counters
from repro.obs.trace import NULL_SPAN
from repro.sched_integration.fabric import pow2_bucket

# Leaf classification by name — the same convention _cache_rule uses.
PAGED_LEAVES = frozenset({"k", "v", "ckv", "kr"})
STATE_LEAVES = frozenset({"conv", "ssm"})


def _leaf_kind(path) -> tuple[bool, bool]:
    """(is_paged, is_stacked) for one cache-tree leaf path."""
    keys = []
    for k in path:
        keys.append(str(getattr(k, "key", getattr(k, "idx", k))))
    name = keys[-1]
    if name in PAGED_LEAVES:
        return True, "stages" in keys
    if name in STATE_LEAVES:
        return False, "stages" in keys
    raise ValueError(f"unknown cache leaf {'/'.join(keys)!r}")


def pool_shapes(cfg, num_pages: int, page_size: int, max_batch: int,
                max_len: int):
    """ShapeDtypeStruct tree of a page pool, mirroring ``model.cache_specs``
    leaf-for-leaf: paged leaves hold ``num_pages + 1`` pages of
    ``page_size`` tokens, state leaves ``max_batch + 1`` slots (the last
    page / slot is the scratch one)."""
    from repro.models.model import cache_specs

    def pool_spec(path, leaf):
        paged, stacked = _leaf_kind(path)
        shape = list(leaf.shape)
        b_ax, s_ax = (1, 2) if stacked else (0, 1)
        if paged:
            shape[b_ax] = num_pages + 1
            shape[s_ax] = page_size
        else:
            shape[b_ax] = max_batch + 1
        return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)

    return tree_map_with_path(pool_spec, cache_specs(cfg, 1, max_len))


@dataclass
class _Slot:
    """Host-side decode state of one in-flight request."""

    prompt: np.ndarray            # (S0,) int32
    new_tokens: int
    pages: list[int]              # reserved page ids (freed at retire)
    tokens: list[int] = field(default_factory=list)   # generated so far

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.new_tokens

    @property
    def write_pos(self) -> int:
        """Cache position the *next* decode tick writes this slot's current
        token at (= S0 + steps already decoded)."""
        return len(self.prompt) + len(self.tokens) - 1


class PagePool:
    """Device-resident page pool + host-side page table and free lists.

    Pure allocation bookkeeping — no model math.  ``num_pages`` defaults to
    full occupancy (``max_batch * pages_per_slot``); configure it lower to
    exercise exhaustion (admission then queues).  The ``allocated`` /
    ``freed`` counters are cumulative page counts; at drain (no slots in
    flight) they must match — the invariant tests assert.
    """

    def __init__(self, cfg, max_batch: int, page_size: int, max_len: int,
                 num_pages: int | None = None, shardings=None):
        if max_len % page_size != 0:
            raise ValueError(
                f"max_len={max_len} must be a multiple of page_size={page_size}")
        self.cfg = cfg
        self.max_batch = int(max_batch)
        self.page_size = int(page_size)
        self.max_len = int(max_len)
        self.pages_per_slot = max_len // page_size
        self.num_pages = int(num_pages if num_pages is not None
                             else max_batch * self.pages_per_slot)
        if self.num_pages < self.pages_per_slot:
            raise ValueError(
                f"num_pages={self.num_pages} cannot hold even one full "
                f"sequence ({self.pages_per_slot} pages)")
        self.scratch_page = self.num_pages          # index of the scratch page
        self.scratch_slot = self.max_batch          # index of the scratch row
        # Page table: scratch row at the end stays all-scratch forever.
        self.table = np.full((self.max_batch + 1, self.pages_per_slot),
                             self.scratch_page, dtype=np.int32)
        self.free_page_ids: deque[int] = deque(range(self.num_pages))
        self.free_slot_ids: deque[int] = deque(range(self.max_batch))
        self.allocated = 0
        self.freed = 0
        self.pools = self._init_pools(shardings)

    def _init_pools(self, shardings=None):
        """Zero pool tree born in ``shardings`` (None: the default device)."""
        shapes = pool_shapes(self.cfg, self.num_pages, self.page_size,
                             self.max_batch, self.max_len)
        return jax.jit(lambda: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes),
            out_shardings=shardings)()

    # -- allocation ---------------------------------------------------------

    def pages_needed(self, total_len: int) -> int:
        return math.ceil(total_len / self.page_size)

    def can_admit(self, total_len: int) -> bool:
        return (len(self.free_slot_ids) > 0
                and len(self.free_page_ids) >= self.pages_needed(total_len))

    def reserve(self, total_len: int) -> tuple[int, list[int]]:
        """Claim a slot and ALL pages ``total_len`` will need.  Caller must
        check :meth:`can_admit` first; raises RuntimeError otherwise."""
        n = self.pages_needed(total_len)
        if not self.can_admit(total_len):
            raise RuntimeError(
                f"pool exhausted: need {n} pages / 1 slot, have "
                f"{len(self.free_page_ids)} pages / "
                f"{len(self.free_slot_ids)} slots")
        slot = self.free_slot_ids.popleft()
        pages = [self.free_page_ids.popleft() for _ in range(n)]
        self.allocated += n
        row = np.full(self.pages_per_slot, self.scratch_page, dtype=np.int32)
        row[:n] = pages
        self.table[slot] = row
        return slot, pages

    def release(self, slot: int, pages: list[int]) -> None:
        self.table[slot] = self.scratch_page
        self.free_page_ids.extend(pages)
        self.free_slot_ids.append(slot)
        self.freed += len(pages)

    @property
    def free_pages(self) -> int:
        return len(self.free_page_ids)

    @property
    def free_slots(self) -> int:
        return len(self.free_slot_ids)


def in_place_layers(cfg, pools, in_place: bool = True) -> dict:
    """Which attention layers the paged tick reads in the pool, by the
    structure of their cache leaves: ``{("first", i) | ("stages", "subj"):
    bool}``, one entry per attention sublayer.

    A layer attends in place when its leaves are a plain paged ``k``/``v``
    pair and it has no local window and no attention soft-cap.  MLA
    (``ckv``/``kr``) and windowed or capped layers gather a dense view, as
    does every layer when ``in_place`` is false (a sharded engine: the
    kernel is not partitioned over KV heads).  State leaves (``conv``/
    ``ssm``) are not attention and have no entry.
    """
    from repro.models.transformer import _sublayer_plan

    def direct(window, sub):
        return (in_place and window != "local" and cfg.attn_softcap is None
                and set(sub["mixer"]) == {"k", "v"})

    out = {("first", i): direct(cfg.window_kind(0), sub)
           for i, sub in enumerate(pools["first"])}
    for j, slot in enumerate(_sublayer_plan(cfg)):
        if slot["kind"] == "attn":
            name = f"sub{j}"
            out[("stages", name)] = direct(slot["window"],
                                           pools["stages"][name])
    return out


def lane_view_bytes(pool: PagePool, in_place: bool = True) -> int:
    """Per device, the bytes of one lane's dense K/V view over the layers
    the tick gathers (:func:`in_place_layers`): a ``B``-lane tick builds
    ``B`` times this, and an in-place tick none.  Read from each pool
    leaf's shard on one device, so a view sharded over KV heads counts
    that device's heads only."""
    direct = in_place_layers(pool.cfg, pool.pools, in_place)
    subs = [(("first", i), t) for i, t in enumerate(pool.pools["first"])]
    subs += [(("stages", n), t) for n, t in pool.pools["stages"].items()]
    total = 0
    for where, tree in subs:
        if direct.get(where):
            continue
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            if _leaf_kind(path)[0]:
                shard = math.prod(leaf.sharding.shard_shape(leaf.shape))
                total += (shard * leaf.dtype.itemsize
                          // (pool.num_pages + 1) * pool.pages_per_slot)
    return total


def compiled_wire_bytes(fn, args) -> int:
    """Per device, the collective wire bytes of the executable of the
    jitted ``fn`` for ``args``, trip-weighted over its loops.  Called after
    ``fn(*args)``: the lowering finds the executable that call built (the
    donated buffers of ``args`` need only their shapes), so nothing
    compiles again."""
    from repro.launch.hlo_analysis import analyze_hlo

    hlo = fn.lower(*args).compile().as_text()
    return int(analyze_hlo(hlo)["total_wire_bytes_per_device"])


def paged_programs(cfg, page_size: int, pages_per_slot: int,
                   in_place: bool = True) -> dict:
    """The paged runtime's device programs as plain (unjitted) functions.

    ``tick``, ``tick_sched``, ``tick_sched_counted``, ``admit_scatter`` and
    ``restore_scatter`` over pool trees of ``page_size``-token pages,
    ``pages_per_slot`` per sequence.  :class:`PagedRuntime` jits them for
    its engine's placement; a compile-only check can lower them from
    abstract shapes for a device that is not attached.  ``in_place``:
    whether the tick may read attention layers in the pool
    (:func:`in_place_layers`); false gathers every layer's dense view.
    """
    from repro.models.attention import PagedKV
    from repro.models.model import decode_step

    pp, ps = pages_per_slot, page_size

    def gather(pools, table, slot_ids):
        """pools + (B, pp) table + (B,) slot ids → the tick's cache tree:
        a :class:`PagedKV` over the pool for each in-place layer, else the
        dense (B, Smax, ...) view of the lanes' pages."""
        B = table.shape[0]
        direct = in_place_layers(cfg, pools, in_place)

        def g(path, pool):
            paged, stacked = _leaf_kind(path)
            if paged:
                if stacked:
                    v = pool[:, table]          # (L, B, pp, ps, ...)
                    return v.reshape(v.shape[0], B, pp * ps,
                                     *v.shape[4:])
                v = pool[table]                 # (B, pp, ps, ...)
                return v.reshape(B, pp * ps, *v.shape[3:])
            return pool[:, slot_ids] if stacked else pool[slot_ids]

        def sub(where, tree):
            if not direct.get(where):
                return tree_map_with_path(lambda p, x: g(where + p, x), tree)
            kv = tree["mixer"]
            if where[0] == "first":             # one layer: give it an axis
                kv = {n: a[None] for n, a in kv.items()}
            return {"mixer": PagedKV(kv["k"], kv["v"], jnp.int32(0), table)}

        return {"first": [sub(("first", i), t)
                          for i, t in enumerate(pools["first"])],
                "stages": {n: sub(("stages", n), t)
                           for n, t in pools["stages"].items()}}

    def scatter_token(pools, new_caches, table, slot_ids, pos):
        """Write back only what the tick changed: the one token each lane
        wrote at ``pos`` (paged leaves) and the rolled state rows.  An
        in-place layer's new token comes as (L, B, ...) or (B, ...), a
        gathered layer's inside its dense (…, B, Smax, ...) view."""
        B = table.shape[0]
        rows = jnp.arange(B)
        page = table[rows, pos // ps]           # (B,) target page ids
        off = pos % ps

        def s(path, pool, new):
            paged, stacked = _leaf_kind(path)
            if paged:
                dense = new.ndim == pool.ndim
                if stacked:
                    return pool.at[:, page, off].set(
                        new[:, rows, pos] if dense else new)
                return pool.at[page, off].set(new[rows, pos] if dense
                                              else new)
            if stacked:
                return pool.at[:, slot_ids].set(new)
            return pool.at[slot_ids].set(new)

        return tree_map_with_path(s, pools, new_caches)

    # Named scopes (HLO metadata only) mark the page gather, the page
    # scatter and the in-tick decision; attention and the FFN are scoped
    # in models/transformer.py.
    def tick(params, pools, table, slot_ids, pos, tok):
        with jax.named_scope("page_gather"):
            caches = gather(pools, table, slot_ids)
        logits, new_caches = decode_step(params, caches, tok, pos, cfg)
        with jax.named_scope("page_scatter"):
            pools = scatter_token(pools, new_caches, table, slot_ids, pos)
        # Greedy selection INSIDE the jitted program: the host only ever
        # transfers the (B,) winning tokens, never the (B, V) logits —
        # same argmax the dense oracle computes, one op earlier
        # (host-sync-in-hot-path design rule; see repro.analysis).
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pools

    # The fused-scheduler tick: the HEFT_RT decision for the next
    # admission batch runs INSIDE the same compiled program as the
    # decode step, against the fabric's device-resident T_avail/mask
    # registers (docs/scheduling.md).  Decode math is byte-for-byte the
    # plain tick's; the decision outputs ride the token transfer the
    # tick already makes, so steady-state serving schedules with zero
    # extra host round-trips.
    def tick_sched(params, pools, table, slot_ids, pos, tok,
                   a_p, ex_p, valid, avail, mask):
        toks, pools = tick(params, pools, table, slot_ids, pos, tok)
        with jax.named_scope("heft_rt_decision"):
            res = decision_ref(a_p, ex_p, avail, valid, mask)
        # Tokens + decision leave the device as ONE packed int32 buffer
        # (see pack_tick_outputs): per-output host syncs would cost more
        # than the decision itself.  new_avail additionally rides out as
        # the live register (donated buffer), never materialized.
        return pack_tick_outputs(toks, res), pools, res.new_avail

    def tick_sched_counted(params, pools, table, slot_ids, pos, tok,
                           a_p, ex_p, valid, avail, mask, counters,
                           p_valid):
        toks, pools = tick(params, pools, table, slot_ids, pos, tok)
        with jax.named_scope("heft_rt_decision"):
            res = decision_ref(a_p, ex_p, avail, valid, mask)
        counters = accumulate_counters(counters, res.assignment,
                                       res.new_avail, valid, p_valid)
        return pack_tick_outputs(toks, res), pools, res.new_avail, counters

    def admit_scatter(pools, dense, table_row, slot):
        """Place one request's freshly prefilled (B=1) dense cache into
        its reserved pages / state slot.  Tail table entries are the
        scratch page, so over-length writes land there harmlessly."""

        def s(path, pool, d):
            paged, stacked = _leaf_kind(path)
            if paged:
                if stacked:
                    v = d[:, 0].reshape(d.shape[0], pp, ps, *d.shape[3:])
                    return pool.at[:, table_row].set(v)
                v = d[0].reshape(pp, ps, *d.shape[2:])
                return pool.at[table_row].set(v)
            if stacked:
                return pool.at[:, slot].set(d[:, 0])
            return pool.at[slot].set(d[0])

        return tree_map_with_path(s, pools, dense)

    def restore_scatter(pools, vals, table_row, slot):
        """Place a snapshotted page set (already page-shaped) back."""

        def s(path, pool, v):
            paged, stacked = _leaf_kind(path)
            if paged:
                if stacked:
                    return pool.at[:, table_row].set(v)
                return pool.at[table_row].set(v)
            if stacked:
                return pool.at[:, slot].set(v)
            return pool.at[slot].set(v)

        return tree_map_with_path(s, pools, vals)

    return {"tick": tick, "tick_sched": tick_sched,
            "tick_sched_counted": tick_sched_counted,
            "admit_scatter": admit_scatter,
            "restore_scatter": restore_scatter}


class PagedRuntime:
    """Continuous-batching decode runtime bound to one ``ServeEngine``.

    Built by :meth:`ServeEngine.start_paged`; the engine's ``admit`` /
    ``decode_tick`` / ``retire`` / ``free_pages`` delegate here.  Holds the
    :class:`PagePool`, the per-slot host decode state, and the compiled
    decode tick (one variant per power-of-two lane bucket).  ``attn`` is
    ``"paged"`` when every attention layer of the tick reads the pool in
    place, else ``"gather"`` (recorded on ``engine.decode_tick``).  On a
    meshed engine each tick also records ``chips``, ``lanes`` (its lane
    bucket), ``view_bytes`` (per chip, the dense K/V view it gathers) and
    ``exchange_bytes`` (per chip, the collective wire bytes of its compiled
    program).
    Decode is greedy (the bitwise-oracle contract is argmax-per-row).
    """

    def __init__(self, engine, max_batch: int, page_size: int,
                 num_pages: int | None = None):
        self.engine = engine
        self.pool = PagePool(engine.cfg, max_batch, page_size, engine.max_len,
                             num_pages=num_pages,
                             shardings=self._pool_shardings())
        self.slots: dict[int, _Slot] = {}
        self._bind()

    # -- compiled steps (rebuilt on reshard) --------------------------------

    def _pool_shardings(self):
        """``page_pspecs`` layouts on the engine's mesh (None unmeshed)."""
        from repro.dist.sharding import named, page_pspecs

        eng = self.engine
        if eng.mesh is None:
            return None
        return named(eng.mesh, page_pspecs(eng.cfg, eng.axes))

    def _bind(self) -> None:
        """(Re)build the jitted tick/admit-scatter for the engine's current
        mesh slice.  Mirrors ``ServeEngine._build``: pool leaves take the
        ``page_pspecs`` layouts, everything else replicates."""
        from repro.dist.sharding import named, replica_pspecs, reshard_tree

        eng = self.engine
        cfg = eng.cfg
        in_place = eng.mesh is None
        fns = paged_programs(cfg, self.pool.page_size,
                             self.pool.pages_per_slot, in_place=in_place)
        # "paged" when every attention layer reads the pool in place.
        direct = in_place_layers(cfg, self.pool.pools, in_place).values()
        self.attn = "paged" if direct and all(direct) else "gather"

        if eng.mesh is not None:
            pool_sh = self._pool_shardings()
            specs = replica_pspecs(cfg, eng.axes, fsdp=eng.fsdp)
            p_sh = named(eng.mesh, specs["params"])
            # Every operand but weights and pool, and every output but the
            # pool, is replicated: the scheduler's registers leave a tick
            # as the next takes them, so re-placing them (``_run_tick``)
            # costs nothing unless a host-path decision moved them.
            rep = self._replicated = NamedSharding(eng.mesh, P())
            with eng._ctx():
                self.pool.pools = reshard_tree(self.pool.pools, pool_sh)
            self.chips = eng.mesh.devices.size
            self._lane_view_bytes = lane_view_bytes(self.pool, in_place)
            self.exchange_bytes = 0
            self._tick = self._exchange_counted(jax.jit(
                fns["tick"],
                in_shardings=(p_sh, pool_sh) + (rep,) * 4,
                out_shardings=(rep, pool_sh), donate_argnums=(1,)))
            # The fabric's T_avail register file (arg 9) and counter file
            # (arg 11) are donated so the registers stay device-resident
            # across ticks.
            self._tick_sched = self._exchange_counted(jax.jit(
                fns["tick_sched"],
                in_shardings=(p_sh, pool_sh) + (rep,) * 9,
                out_shardings=(rep, pool_sh, rep),
                donate_argnums=(1, 9)))
            self._tick_sched_counted = self._exchange_counted(jax.jit(
                fns["tick_sched_counted"],
                in_shardings=(p_sh, pool_sh) + (rep,) * 11,
                out_shardings=(rep, pool_sh, rep, rep),
                donate_argnums=(1, 9, 11)))
            self._admit_scatter = jax.jit(
                fns["admit_scatter"],
                in_shardings=(pool_sh, eng._cache_sh, None, None),
                out_shardings=pool_sh, donate_argnums=(0,))
            self._restore_scatter = jax.jit(
                fns["restore_scatter"],
                in_shardings=(pool_sh, None, None, None),
                out_shardings=pool_sh, donate_argnums=(0,))
        else:
            self.chips = None
            self._tick = jax.jit(fns["tick"], donate_argnums=(1,))
            self._tick_sched = jax.jit(fns["tick_sched"],
                                       donate_argnums=(1, 9))
            self._tick_sched_counted = jax.jit(fns["tick_sched_counted"],
                                               donate_argnums=(1, 9, 11))
            self._admit_scatter = jax.jit(fns["admit_scatter"],
                                          donate_argnums=(0,))
            self._restore_scatter = jax.jit(fns["restore_scatter"],
                                            donate_argnums=(0,))
        # Scratch-page id, exposed for tests/introspection.
        self.scratch_page = self.pool.scratch_page

    def _exchange_counted(self, fn):
        """A meshed tick program that reads, once per lane bucket, the
        collective wire bytes per chip of its compiled executable,
        trip-weighted over the layer scan (:func:`compiled_wire_bytes`),
        into ``exchange_bytes``."""
        wire = {}

        def call(*args):
            out = fn(*args)
            B = args[2].shape[0]
            if B not in wire:
                wire[B] = compiled_wire_bytes(fn, args)
            self.exchange_bytes = wire[B]
            return out

        return call

    def rebind(self) -> None:
        """Re-place the pools and rebuild the tick after an engine reshard.

        The page set migrates as a unit through ``reshard_tree`` (or a host
        round-trip when moving off-mesh) — in-flight requests keep decoding
        token-identically on the new slice.
        """
        if self.engine.mesh is None:
            self.pool.pools = jax.tree.map(
                lambda x: jnp.asarray(np.asarray(x)), self.pool.pools)
        self._bind()

    # -- in-flight API ------------------------------------------------------

    def admit(self, prompt: np.ndarray, new_tokens: int) -> int | None:
        """Prefill + join the running batch.  Returns the slot id, or None
        when the pool cannot hold the request (caller queues — never drops).

        Reserves every page the request will need up front, so decode can
        never hit exhaustion mid-flight.  The first generated token comes
        from the prefill logits (argmax), exactly as the dense oracle's
        ``generate`` computes it.
        """
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        total = len(prompt) + int(new_tokens)
        if total > self.pool.max_len:
            raise ValueError(f"S0+new_tokens={total} exceeds "
                             f"max_len={self.pool.max_len}")
        if new_tokens < 1:
            raise ValueError("new_tokens must be >= 1")
        if not self.pool.can_admit(total):
            return None
        slot, pages = self.pool.reserve(total)
        eng = self.engine
        with eng._ctx():
            logits, dense = eng._prefill(eng.params, jnp.asarray(prompt[None]))
            self.pool.pools = self._admit_scatter(
                self.pool.pools, dense, jnp.asarray(self.pool.table[slot]),
                jnp.int32(slot))
            first = int(jnp.argmax(logits[0]))
        self.slots[slot] = _Slot(prompt=prompt, new_tokens=int(new_tokens),
                                 pages=pages, tokens=[first])
        return slot

    def active_slots(self) -> list[int]:
        """Slots that still need decode ticks (not yet done)."""
        return sorted(s for s, rec in self.slots.items() if not rec.done)

    def finished_slots(self) -> list[int]:
        """Slots whose generation is complete and awaiting :meth:`retire`."""
        return sorted(s for s, rec in self.slots.items() if rec.done)

    def decode_tick(self, sched=None):
        """One decode step for every active slot: ``decode_step`` with
        per-row positions over the pool (in place, or through a gathered
        dense view), then the written token scattered to its page.  Returns {slot: newly generated token}.  Lane count pads to
        the next power of two (scratch-slot lanes), so admissions change the
        compiled variant at most ``log2(max_batch)+1`` times.

        ``sched``: optional staged HEFT_RT mapping event ``(avg,
        exec_times, fabric)`` — a *fused-backend* :class:`repro.
        sched_integration.fabric.MappingFabric` whose device registers the
        tick consumes.  The decision runs inside the same compiled program
        as the decode step (zero extra host round-trips; its outputs ride
        the token transfer), and the return value becomes ``(tokens,
        decision)`` with ``decision`` the fabric's ``map_event`` 5-tuple.
        Decode math is byte-for-byte the plain tick's.
        """
        active = self.active_slots()
        if not active:
            return {} if sched is None else ({}, None)
        tr = self.engine.tracer
        if tr is None:
            return self._run_tick(active, sched, None)
        ps = self.pool.page_size
        reserved = sum(len(self.slots[s].pages) for s in active)
        written = sum(self.slots[s].write_pos // ps + 1 for s in active)
        with tr.span("engine.decode_tick", active=len(active),
                     fused=sched is not None, pages_reserved=reserved,
                     pages_written=written, attn=self.attn) as span:
            out = self._run_tick(active, sched, tr)
            if self.chips is not None:
                B = pow2_bucket(len(active), 1)
                span.set(chips=self.chips, lanes=B,
                         view_bytes=B * self._lane_view_bytes,
                         exchange_bytes=self.exchange_bytes)
            return out

    def _run_tick(self, active: list[int], sched, tr):
        """:meth:`decode_tick` over the live slots ``active``; ``tr`` (a
        tracer, or None) times its ``tick.stage`` / ``tick.wait`` /
        ``tick.commit`` phases.  The dispatch of the jitted call lies
        between stage and wait."""
        stage = NULL_SPAN if tr is None else tr.phase("tick.stage")
        stage.__enter__()
        B = pow2_bucket(len(active), 1)
        scratch = self.pool.scratch_slot
        lanes = active + [scratch] * (B - len(active))
        slot_ids = np.asarray(lanes, dtype=np.int32)
        pos = np.zeros(B, dtype=np.int32)
        tok = np.zeros((B, 1), dtype=np.int32)
        for i, s in enumerate(active):
            rec = self.slots[s]
            pos[i] = rec.write_pos
            tok[i, 0] = rec.tokens[-1]
        eng = self.engine
        decision = None
        with eng._ctx():
            args = (eng.params, self.pool.pools,
                    jnp.asarray(self.pool.table[slot_ids]),
                    jnp.asarray(slot_ids), jnp.asarray(pos), jnp.asarray(tok))
            if sched is None:
                stage.__exit__(None, None, None)
                toks, self.pool.pools = self._tick(*args)
                with NULL_SPAN if tr is None else tr.phase("tick.wait"):
                    nxt = np.asarray(toks)
                commit = NULL_SPAN if tr is None else tr.phase("tick.commit")
                commit.__enter__()
            else:
                avg, exec_times, fab = sched
                n = len(avg)
                (a_p, ex_p, valid, avail, mask,
                 counters, p_valid) = fab.tick_decision_inputs(avg, exec_times)
                if self.chips is not None:
                    # A host-path decision leaves the registers on one
                    # device; back on the mesh they meet the executable
                    # their bucket compiled, not a second variant.
                    avail, counters = jax.device_put((avail, counters),
                                                     self._replicated)
                stage.__exit__(None, None, None)
                if counters is None:
                    packed, self.pool.pools, new_avail = self._tick_sched(
                        *args, a_p, ex_p, valid, avail, mask)
                    ctr = None
                else:
                    # Exclusive branch: only one tick variant dispatches, so
                    # the staged operands feed exactly one donated call.
                    (packed, self.pool.pools, new_avail,
                     ctr) = self._tick_sched_counted(
                        *args, a_p, ex_p, valid, avail, mask,  # repro: noqa[donation-after-use]
                        counters, p_valid)
                # The tick's single host sync: tokens and decision share one
                # packed buffer (pack_tick_outputs); new_avail/ctr stay
                # device-resident and are adopted back by the fabric.
                with NULL_SPAN if tr is None else tr.phase("tick.wait"):
                    buf = np.asarray(packed)
                commit = NULL_SPAN if tr is None else tr.phase("tick.commit")
                commit.__enter__()
                nxt = buf[:B]
                decision = fab.commit_tick_decision(n, buf[B:], new_avail,
                                                    ctr)
        out = {}
        for i, s in enumerate(active):
            t = int(nxt[i])
            self.slots[s].tokens.append(t)
            out[s] = t
        commit.__exit__(None, None, None)
        return out if sched is None else (out, decision)

    def retire(self, slot: int) -> np.ndarray:
        """Free the slot's pages and return the full (S0+new_tokens,) ids."""
        rec = self.slots.pop(slot)
        self.pool.release(slot, rec.pages)
        return np.concatenate([rec.prompt,
                               np.asarray(rec.tokens, dtype=np.int32)])

    # -- pages as the migration / recovery unit -----------------------------

    def snapshot_slot(self, slot: int) -> dict:
        """Host-side snapshot of ONE request: its page set (page-shaped, not
        the dense cache) + decode state.  O(request length), not O(pool)."""
        rec = self.slots[slot]
        row = self.pool.table[slot]

        def snap(path, pool):
            paged, stacked = _leaf_kind(path)
            a = np.asarray(pool)
            if paged:
                return a[:, row] if stacked else a[row]
            return a[:, slot] if stacked else a[slot]

        return {
            "pages": tree_map_with_path(snap, self.pool.pools),
            "prompt": rec.prompt.copy(),
            "new_tokens": rec.new_tokens,
            "tokens": list(rec.tokens),
        }

    def restore_slot(self, snap: dict) -> int | None:
        """Re-admit a :meth:`snapshot_slot` request into THIS pool (same or a
        different engine).  Returns the new slot id, or None if the pool
        cannot hold it right now (caller queues).  Decoding resumes
        token-identically from the last committed token."""
        total = len(snap["prompt"]) + int(snap["new_tokens"])
        if not self.pool.can_admit(total):
            return None
        slot, pages = self.pool.reserve(total)
        with self.engine._ctx():
            self.pool.pools = self._restore_scatter(
                self.pool.pools,
                jax.tree.map(jnp.asarray, snap["pages"]),
                jnp.asarray(self.pool.table[slot]), jnp.int32(slot))
        self.slots[slot] = _Slot(prompt=np.asarray(snap["prompt"],
                                                   dtype=np.int32),
                                 new_tokens=int(snap["new_tokens"]),
                                 pages=pages, tokens=list(snap["tokens"]))
        return slot
