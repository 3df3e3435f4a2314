"""Checkpoint/resume (SURVEY.md §5.4 — the reference has model
persistence by contract but NO training checkpointing; here training
state checkpoints ride orbax, the TPU-native answer, with the same
save/restore surface the estimators use for models).

Works with sharded (GSPMD) params: orbax restores to the same
shardings when given an abstract target. Two distributed regimes are
handled distinctly (see ``TrainCheckpointer.__init__``):

- **HorovodRunner gangs** (``hvd.init()`` called): one jax world with
  state replicated per rank. ``hvd.rank()`` is the launcher's rank and
  need NOT equal ``jax.process_index()`` (a TPU runtime numbers the
  processes by where their chips sit), so who writes is decided by the
  hvd rank alone: rank 0 persists (:func:`should_save`). Each rank's
  manager is pinned process-local — by its runtime index, which is what
  orbax knows — so orbax's cross-process barriers don't deadlock when
  the other ranks skip the write.
- **Multihost GSPMD pjit jobs** (multi-process world, no hvd gang):
  arrays are sharded across processes, so ALL processes must
  participate in each save; orbax's default cross-process coordination
  is left in place.

Elastic resume (ISSUE 15): every :meth:`TrainCheckpointer.save` also
writes a **sharding-tree sidecar** — a jax-free, schema-versioned JSON
(``sharding_tree-<step>.json``) recording each leaf's full shape/dtype
and per-dim mesh-axis spec plus the mesh axis sizes the run was laid
out on. The sidecar is durable *before* orbax commits the step (orbax
commits by renaming the temp dir to the bare step number), so
:func:`latest_complete_step` semantics are preserved: a numeric step
dir existing implies its sidecar exists. On restore,
``restore(..., target_mesh=...)`` re-lays every param onto whatever
mesh the surviving world built — the paper's ``np=-1`` ("use what the
cluster has") contract made true end-to-end: a preempted gang
relaunched at a different np restores straight onto the shrunken (or
regrown) mesh, honoring the reshard plan's restore-time HBM high-water
mark by placing param groups one at a time when memory is tight.
"""

import json
import logging
import os
import time

logger = logging.getLogger("HorovodRunner")

SHARDING_TREE_SCHEMA = "sparkdl_tpu.checkpoint.sharding_tree/1"


class ReshardRestoreError(RuntimeError):
    """A resharded restore failed for a reason that is NOT a corrupt
    step artifact (metadata unavailable in a world that needs it, the
    grouped-placement accounting invariant broken). Deliberately
    excluded from :meth:`TrainCheckpointer.restore`'s corrupt-step
    fallback: retrying earlier steps would fail identically, and
    quarantining them would destroy healthy checkpoints."""


def _process_index():
    """This process's index in the jax world (0 when not distributed).
    It is what orbax pins a gang rank's manager to, and who writes the
    sidecar in a GSPMD job; it is never compared with an hvd rank."""
    try:
        import jax

        return jax.process_index()
    except Exception:
        return 0


def should_save():
    """In a gang, only rank 0 persists (workers hold replicated state)."""
    from sparkdl_tpu.hvd import _state

    st = _state.state()
    return (not st.initialized) or st.rank == 0


def _writes_sidecar():
    """The one process that writes the files orbax does not: the
    gang's rank 0 — whatever index the runtime gave it — or process 0
    of a GSPMD job, where every process saves."""
    from sparkdl_tpu.hvd import _state

    st = _state.state()
    return st.rank == 0 if st.initialized else _process_index() == 0


def latest_complete_step(directory):
    """Newest COMMITTED checkpoint step under a TrainCheckpointer
    root, by directory scan alone — no orbax (or jax) import, so the
    gang supervisor can call it from the driver between relaunches
    without initializing a backend the workers need. Orbax commits a
    step by renaming its temp dir (suffixed, non-numeric) to the bare
    step number, so numeric-named directories are exactly the durable
    steps; a worker preempted mid-save leaves only a temp dir, which
    this scan correctly ignores. Returns None when no step exists."""
    try:
        names = os.listdir(directory)
    except OSError:
        return None
    steps = [
        int(n) for n in names
        if n.isdigit() and os.path.isdir(os.path.join(directory, n))
    ]
    return max(steps, default=None)


def _committed_steps(directory):
    """All committed step numbers under a checkpoint root, by the same
    numeric-dir scan as :func:`latest_complete_step`."""
    try:
        names = os.listdir(directory)
    except OSError:
        return []
    return sorted(
        int(n) for n in names
        if n.isdigit() and os.path.isdir(os.path.join(directory, n))
    )


def sharding_sidecar_path(directory, step):
    """Path of one step's sharding-tree sidecar under a checkpoint
    root. Kept beside (not inside) the orbax step dir: the sidecar is
    written and durable BEFORE orbax's commit rename, so the
    numeric-dir-implies-committed invariant of
    :func:`latest_complete_step` extends to the sidecar."""
    return os.path.join(directory, f"sharding_tree-{int(step)}.json")


def load_sharding_tree(directory, step):
    """Load one step's sharding-tree sidecar, or None (absent, torn,
    or schema-mismatched — a pre-elastic checkpoint restores without
    resharding). jax-free on purpose: the gang supervisor calls this
    on the driver, between relaunches, to derive the surviving mesh
    for the restart context without initializing a backend."""
    try:
        with open(sharding_sidecar_path(directory, step)) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(doc, dict) or doc.get("schema") != SHARDING_TREE_SCHEMA:
        return None
    return doc


def sidecar_mesh_axes(doc):
    """The sidecar's recorded mesh axis sizes as a plain
    ``{name: size}`` dict — the one normalization point for the
    schema field (checkpoint restore, the supervisor's restart
    context, and the analysis sidecar reader all share it)."""
    return {
        str(k): int(v)
        for k, v in ((doc or {}).get("mesh_axes") or {}).items()
    }


class TrainCheckpointer:
    """Step-indexed train-state checkpoints (params, opt_state, extras).

    Thin wrapper over ``orbax.checkpoint.CheckpointManager`` with
    keep-last-N retention and atomic writes.
    """

    def __init__(self, directory, max_to_keep=3, async_save=False):
        """``async_save=True`` returns from :meth:`save` as soon as the
        state is snapshotted to host memory; the disk write proceeds in
        the background (orbax AsyncCheckpointer) so the train loop's
        next step overlaps the IO instead of stalling on it. Restores,
        a following save, and :meth:`close` all join the pending write
        first.

        Gang semantics: a HorovodRunner gang is one jax world
        (``hvd.init()`` calls ``jax.distributed.initialize``; the
        runtime's process index need not be the hvd rank) with state
        REPLICATED per rank, so each rank's manager is pinned to its
        own process (orbax's cross-process barriers would otherwise
        deadlock: the non-primary rank skips the write without
        entering the barrier the primary waits in). Rank 0 persists
        (:func:`should_save` gates :meth:`save`); any rank may
        :meth:`restore`, ordered by the caller (``hvd.barrier()``
        between a save and a dependent restore).

        Multihost GSPMD pjit jobs (multi-process world WITHOUT an hvd
        gang) keep orbax's default cross-process coordination: arrays
        are sharded across processes, so every process must join each
        save — pinning here would make each process its own primary
        and corrupt/thin the write.

        The regime is decided LAZILY at the first save/restore, not at
        construction: a checkpointer built before ``hvd.init()`` in a
        gang worker would otherwise latch the GSPMD branch, and its
        first rank-0-only save would deadlock in orbax's cross-process
        barrier — exactly the failure the pinning exists to prevent."""
        self._dir = os.path.abspath(directory)
        self._async = bool(async_save)
        self._max_to_keep = max_to_keep
        os.makedirs(self._dir, exist_ok=True)
        self._mgr_instance = None
        self._gang = None
        # Stats of the most recent resharded restore (None when the
        # last restore needed none): direction, axes, bytes moved, and
        # the memory-accounted high water vs the plan's bound — what
        # the chaos acceptance asserts on and the gang.reshard
        # timeline event carries.
        self.last_reshard = None
        # The step the most recent restore() actually loaded: on a
        # corrupt-step fallback this is EARLIER than the requested
        # step, and callers tracking a resume point must re-sync from
        # it rather than from what they asked for.
        self.last_restored_step = None

    @property
    def _mgr(self):
        from sparkdl_tpu.hvd import _state

        if (self._mgr_instance is not None and not self._gang
                and _state.state().initialized):
            # hvd.init() ran AFTER the manager first materialized
            # (e.g. a pre-init latest_step() probed for a resume
            # point): rebuild with gang pinning, or the next
            # rank-0-only save deadlocks in orbax's cross-process
            # barrier. The uninitialized→initialized transition only
            # happens once, and only in a then-single-process world,
            # so the close is barrier-free.
            self._mgr_instance.close()
            self._mgr_instance = None
        if self._mgr_instance is None:
            import orbax.checkpoint as ocp

            self._gang = gang = _state.state().initialized
            if gang:
                pidx = _process_index()
                mp_options = ocp.options.MultiprocessingOptions(
                    primary_host=pidx,
                    active_processes={pidx},
                    barrier_sync_key_prefix=f"rank{pidx}",
                )
            else:
                mp_options = ocp.options.MultiprocessingOptions()
            self._mgr_instance = ocp.CheckpointManager(
                self._dir,
                options=ocp.CheckpointManagerOptions(
                    # the root dir is created in __init__ (orbax's
                    # create=True is unsupported with active_processes
                    # pinned)
                    max_to_keep=self._max_to_keep, create=False,
                    enable_async_checkpointing=self._async,
                    multiprocessing_options=mp_options,
                ),
                # Pre-register the handler: a manager that never saved
                # in this process (every relaunched worker) can
                # otherwise neither read item_metadata nor restore
                # without args — both of which the resharded-restore
                # path needs before any save happens.
                item_handlers=ocp.StandardCheckpointHandler(),
            )
        return self._mgr_instance

    @staticmethod
    def _sharding_tree_doc(step, state):
        """The sharding tree **as data** for one save: per-leaf full
        shape/dtype and per-dim mesh-axis-name spec (``[]`` = that dim
        unsharded), plus the union of mesh axis sizes the leaves were
        laid out on — the serialization
        :func:`sparkdl_tpu.parallel.sharding.sharding_tree_info`
        established, flattened to plain JSON so the sidecar loads
        without jax."""
        import jax

        leaves, _ = jax.tree_util.tree_flatten_with_path(state)
        params = []
        mesh_axes = {}
        for path, leaf in leaves:
            shape = tuple(int(d) for d in getattr(leaf, "shape", ()))
            spec_dims = [[] for _ in shape]
            sh = getattr(leaf, "sharding", None)
            if sh is not None and hasattr(sh, "spec") \
                    and hasattr(sh, "mesh"):
                sizes = dict(zip(sh.mesh.axis_names,
                                 sh.mesh.devices.shape))
                for k, v in sizes.items():
                    mesh_axes[str(k)] = int(v)
                for dim, entry in enumerate(sh.spec):
                    if dim >= len(spec_dims):
                        break
                    names = (entry if isinstance(entry, tuple)
                             else (entry,))
                    spec_dims[dim] = [str(n) for n in names
                                      if n is not None]
            params.append({
                "path": jax.tree_util.keystr(path),
                "shape": list(shape),
                "dtype": str(getattr(leaf, "dtype", "float32")),
                "spec": spec_dims,
            })
        return {
            "schema": SHARDING_TREE_SCHEMA,
            "step": int(step),
            "mesh_axes": mesh_axes,
            "params": params,
        }

    def _write_sidecar(self, step, doc):
        """Atomic (tmp + rename) sidecar write BEFORE the orbax save:
        the numeric step dir only appears after orbax's commit rename,
        so a step visible to :func:`latest_complete_step` always has
        its sidecar on disk. Also prunes sidecars whose step the
        retention policy already deleted."""
        path = sharding_sidecar_path(self._dir, step)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        live = set(_committed_steps(self._dir))
        live.add(int(step))
        try:
            for name in os.listdir(self._dir):
                if (name.startswith("sharding_tree-")
                        and name.endswith(".json")):
                    stem = name[len("sharding_tree-"):-len(".json")]
                    if stem.isdigit() and int(stem) not in live:
                        os.unlink(os.path.join(self._dir, name))
        except OSError:
            pass  # best-effort: a stale sidecar is never load-bearing

    @staticmethod
    def _gather_cross_process(state):
        """Gang regime only: leaves sharded ACROSS the gang's
        processes cannot be written by the rank-0-pinned manager (rank
        0 holds only its own shard), so every rank joins a replicating
        identity jit (an all-gather on the wire) and the full host
        value is what rank 0 persists. The sharding-tree sidecar —
        built from the ORIGINAL leaves before this gather — is what
        lets restore re-lay them. Collective: all ranks must call
        save() (they already do; :func:`should_save` gates the write
        after this). No-op outside a gang or for fully-addressable
        trees, so GSPMD multi-process jobs keep orbax's native
        cross-process save path."""
        from sparkdl_tpu.hvd import _state

        if not _state.state().initialized:
            return state

        def cross_process(leaf):
            return (hasattr(leaf, "is_fully_addressable")
                    and not leaf.is_fully_addressable)

        import jax

        if not any(cross_process(leaf)
                   for leaf in jax.tree_util.tree_leaves(state)):
            return state
        from sparkdl_tpu.parallel.sharding import full_host_value

        return jax.tree_util.tree_map(
            lambda leaf: full_host_value(leaf) if cross_process(leaf)
            else leaf, state)

    def save(self, step, state, force=False):
        """state: any pytree (e.g. {'params': ..., 'opt_state': ...}).
        Blocks until durable unless ``async_save`` was set."""
        import orbax.checkpoint as ocp

        from sparkdl_tpu import observe

        # Sidecar doc from the ORIGINAL leaves (the gather below strips
        # their shardings); the cross-process gather itself is a
        # collective every rank joins before the rank-0 write gate.
        sidecar = self._sharding_tree_doc(step, state)
        state = self._gather_cross_process(state)
        if not should_save():
            return False
        if _writes_sidecar():
            self._write_sidecar(step, sidecar)
        t0 = time.perf_counter()
        if self._async:
            # An async save() returns once the state is snapshotted to
            # host memory — a host-side detour inside the step window,
            # so it is attributed as ``cat="host"`` (the perf
            # report's host_callback component) rather than claiming
            # the background write's dispatch as checkpoint wait.
            with observe.host_span("checkpoint.snapshot",
                                   step=int(step)):
                saved = self._mgr.save(
                    step, args=ocp.args.StandardSave(state), force=force
                )
        else:
            # Sync mode: the span covers snapshot + durable write.
            # Counter + duration histogram feed the alertable view (a
            # checkpoint stall is a classic silent gang killer).
            with observe.span("checkpoint.save", cat="checkpoint",
                              step=int(step), sync=True):
                saved = self._mgr.save(
                    step, args=ocp.args.StandardSave(state), force=force
                )
                self._mgr.wait_until_finished()
        if saved:
            observe.inc("checkpoint_saves_total")
            observe.observe_value(
                "checkpoint_save_seconds", time.perf_counter() - t0
            )
        return saved

    def wait_until_finished(self):
        """Join any in-flight async write (no-op when idle)."""
        self._mgr.wait_until_finished()

    def latest_step(self):
        if self._async:
            self._mgr.wait_until_finished()
        self._refresh_if_reader()
        return self._mgr.latest_step()

    def _refresh_if_reader(self):
        """Gang non-writers: this manager's step bookkeeping was
        scanned at construction; rescan so steps rank 0 wrote since
        (or retention deleted since) are visible. Ordering between a
        write and a dependent read is the caller's barrier. (GSPMD
        jobs write from every process — orbax keeps them in sync.)"""
        mgr = self._mgr  # materialize first (decides the regime)
        if self._gang and not should_save():
            mgr.reload()

    def restore(self, step=None, target=None, *, target_mesh=None,
                fallback=True):
        """Restore a step (default latest). Pass ``target`` (a pytree of
        like-shaped arrays or jax.ShapeDtypeStruct with shardings) to
        control placement of the restored arrays.

        ``target_mesh``: re-lay every param onto this mesh using the
        step's sharding-tree sidecar (elastic resume). When the
        recorded mesh axes differ from the target's, the restore is a
        **reshard**: params land directly on the new mesh, a
        ``gang.reshard`` span with bytes-moved/high-water lands on the
        timeline, ``gang_reshards_total{direction=shrink|grow}``
        counts it, and :attr:`last_reshard` carries the accounting.
        Memory is bounded by the reshard plan's
        ``restore_high_water_bytes``: when that approaches the HBM
        budget (or ``SPARKDL_TPU_RESHARD_GROUPED`` forces it), params
        are placed group-at-a-time instead of materializing old+new
        shards for the whole tree at once.

        ``fallback=True`` (default): if restoring the chosen step
        raises — a torn write that still got a numeric dir name — log
        loudly and fall back to the previous committed step rather
        than burning the gang's whole retry budget on the same
        poisoned checkpoint. The step actually loaded lands in
        :attr:`last_restored_step`; a caller deriving its resume point
        from the requested step must re-sync from it. Typed reshard
        refusals (:class:`~sparkdl_tpu.analysis.comms.
        ReshardPreflightError`, :class:`ReshardRestoreError`) are
        NEVER treated as corruption — they surface immediately. Pass
        ``fallback=False`` to surface any error for exactly the
        requested step.
        """
        if self._async:
            # join any in-flight write: orbax registers the step in its
            # bookkeeping synchronously, so without this a restore
            # could target a step still being committed
            self._mgr.wait_until_finished()
        self._refresh_if_reader()
        if step is None:
            step = self._mgr.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"no checkpoints found under {self._dir}"
            )
        from sparkdl_tpu import observe

        candidates = [int(step)]
        if fallback:
            candidates += [
                s for s in sorted(_committed_steps(self._dir),
                                  reverse=True)
                if s < int(step)
            ]
        from sparkdl_tpu.analysis.comms import ReshardPreflightError

        first_error = None
        for i, cand in enumerate(candidates):
            try:
                with observe.span("checkpoint.restore", cat="checkpoint",
                                  step=int(cand)):
                    observe.inc("checkpoint_restores_total")
                    out = self._restore_step(cand, target, target_mesh)
                    # The step actually loaded — on a fallback this is
                    # EARLIER than requested; resume-step bookkeeping
                    # must re-sync from here, not from what it asked.
                    self.last_restored_step = int(cand)
                    return out
            except (ReshardPreflightError, ReshardRestoreError):
                # Deterministic reshard refusals, not corruption:
                # every candidate would fail identically, and the
                # quarantine below would destroy healthy checkpoints.
                # Surface the typed error to the operator untouched.
                raise
            except Exception as e:  # noqa: BLE001 — every restore
                # failure mode (torn zarr, missing msgpack, orbax
                # version skew) must reach the fallback, or one
                # poisoned step kills the gang's whole retry budget.
                first_error = first_error or e
                if i + 1 >= len(candidates):
                    break
                observe.inc("checkpoint_corrupt_steps_total")
                observe.instant(
                    "checkpoint.corrupt_step", cat="checkpoint",
                    step=int(cand), error=f"{type(e).__name__}: {e}",
                    fallback_step=int(candidates[i + 1]),
                )
                logger.error(
                    "checkpoint step %d under %s failed to restore "
                    "(%s: %s) — falling back to committed step %d "
                    "instead of retrying the poisoned step",
                    cand, self._dir, type(e).__name__, e,
                    candidates[i + 1],
                )
                self._quarantine_step(cand)
        raise first_error

    def _quarantine_step(self, step):
        """Move a torn-but-numeric step dir out of the numeric
        namespace (``<step>.corrupt-<pid>``) and rebuild the manager.
        Both halves matter: orbax latches its item-layout detection
        from EVERY numeric dir at manager construction, so one torn
        step poisons restores of perfectly good steps through the same
        manager — and ``latest_complete_step`` (the supervisor's
        resume-point scan) would keep steering every relaunch back to
        the poison. Racing ranks are fine: the first rename wins,
        the rest ENOENT quietly."""
        path = os.path.join(self._dir, str(int(step)))
        try:
            os.replace(path, f"{path}.corrupt-{os.getpid()}")
            logger.error(
                "quarantined torn checkpoint step dir %s", path,
            )
        except OSError:
            pass
        if self._mgr_instance is not None:
            try:
                self._mgr_instance.close()
            except Exception:  # noqa: BLE001 — a wedged manager must
                pass           # not block the rebuild
            self._mgr_instance = None

    def _restore_step(self, step, target, target_mesh):
        import orbax.checkpoint as ocp

        if target_mesh is None:
            if target is not None:
                return self._mgr.restore(
                    step, args=ocp.args.StandardRestore(target)
                )
            return self._mgr.restore(step)
        return self._resharded_restore(step, target, target_mesh)

    def _resharded_restore(self, step, target, target_mesh):
        """Re-lay step ``step`` onto ``target_mesh`` per the sidecar.

        The restore-time half of the PR 8 pre-flight: the plan that
        proved the shrink feasible (per-dim divisibility, HBM
        high-water) is recomputed here over the actual saved tree
        (``state_multiplier=1.0`` — the tree IS the state) and its
        ``restore_high_water_bytes`` is the budget the placement loop
        accounts against. Grouped placement (old shard + new shard of
        one param GROUP resident at a time, not the whole tree) kicks
        in when the high water approaches the HBM budget or when
        ``SPARKDL_TPU_RESHARD_GROUPED`` pins a group size."""
        import orbax.checkpoint as ocp

        from sparkdl_tpu import observe
        from sparkdl_tpu.analysis.comms import (
            ReshardPreflightError,
            param_info_from_sidecar,
            reshard_plan,
        )
        from sparkdl_tpu.utils import knobs

        doc = load_sharding_tree(self._dir, step)
        target_axes = {
            str(k): int(v)
            for k, v in zip(target_mesh.axis_names,
                            target_mesh.devices.shape)
        }
        if doc is None:  # pre-elastic checkpoint
            # Pre-elastic checkpoint (no sidecar): nothing recorded to
            # reshard FROM. Degrade loudly to the plain restore path.
            logger.warning(
                "no sharding sidecar for step %d under %s — restoring "
                "without resharding (pre-elastic checkpoint)",
                step, self._dir,
            )
            return self._restore_step(step, target, None)
        source_axes = sidecar_mesh_axes(doc)
        info = param_info_from_sidecar(doc)
        plan = reshard_plan(
            info, source_axes or target_axes, target_axes,
            state_multiplier=1.0,
        )
        if not plan.feasible:
            # Same typed refusal as the supervisor pre-flight: an
            # indivisible dim or an over-budget high water must never
            # become an OOM or a sharding crash on the chips.
            raise ReshardPreflightError(plan.problems, plan=plan)

        def world(axes):
            n = 1
            for v in axes.values():
                n *= int(v)
            return n

        src_world, tgt_world = world(source_axes), world(target_axes)
        aligned = source_axes == target_axes
        direction = ("grow" if tgt_world > src_world
                     else "shrink" if tgt_world < src_world
                     else "relayout")
        spec_by_path = {
            p["path"]: p.get("spec") or [] for p in doc["params"]
        }
        group = knobs.read_int("SPARKDL_TPU_RESHARD_GROUPED", 0) or 0
        if group <= 0:
            # Auto: place one param at a time only when the whole-tree
            # worst case (old + new shard of EVERYTHING resident)
            # threatens the HBM budget; otherwise one shot.
            tight = (plan.hbm_bytes and plan.restore_high_water_bytes
                     > 0.5 * plan.hbm_bytes)
            group = 1 if tight else 0

        t_wall = time.time()
        t0 = time.perf_counter()
        if not group and not self._gang and target is not None:
            # Direct path: abstract targets with the re-laid
            # NamedShardings straight through orbax — every param
            # lands on the new mesh with no host detour. Gang ranks
            # skip this (their managers are process-pinned; orbax
            # cannot coordinate a cross-process placement there) and
            # take the host-mediated loop below instead.
            restored, stats = self._direct_resharded(
                step, target, target_mesh, spec_by_path, plan)
        else:
            restored, stats = self._grouped_resharded(
                step, target_mesh, spec_by_path, source_axes,
                target_axes, plan, group)
        if aligned:
            # Same topology: the params landed on their recorded
            # layout — a resume, not a reshard. No span, no counter.
            self.last_reshard = None
            return restored
        stats.update(
            step=int(step), direction=direction,
            source_axes=source_axes, target_axes=target_axes,
            restore_high_water_bytes=plan.restore_high_water_bytes,
            hbm_bytes=plan.hbm_bytes,
        )
        self.last_reshard = stats
        observe.complete(
            "gang.reshard", t_wall, time.perf_counter() - t0,
            cat="checkpoint", **stats,
        )
        observe.inc("gang_reshards_total", direction=direction)
        logger.info(
            "resharded restore of step %d: %s %s -> %s (%d param(s), "
            "%d group(s), %.1f MiB moved, accounted high-water "
            "%.1f MiB within plan %.1f MiB)",
            step, direction, source_axes, target_axes,
            stats["params"], stats["groups"],
            stats["bytes_moved"] / 2**20,
            stats["high_water_accounted_bytes"] / 2**20,
            plan.restore_high_water_bytes / 2**20,
        )
        return restored

    def _direct_resharded(self, step, target, target_mesh,
                          spec_by_path, plan):
        """One-shot orbax restore into sharded abstract targets."""
        import jax
        import orbax.checkpoint as ocp

        from sparkdl_tpu.parallel.sharding import named_sharding_for

        leaves, treedef = jax.tree_util.tree_flatten_with_path(target)
        abstract = jax.tree_util.tree_unflatten(treedef, [
            jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype,
                sharding=named_sharding_for(
                    target_mesh,
                    spec_by_path.get(jax.tree_util.keystr(path))),
            )
            for path, leaf in leaves
        ])
        restored = self._mgr.restore(
            step, args=ocp.args.StandardRestore(abstract)
        )
        return restored, {
            "mode": "direct", "params": len(leaves), "groups": 1,
            "bytes_moved": plan.per_device_bytes_target,
            # One shot = the plan's own worst case is the bound.
            "high_water_accounted_bytes": plan.restore_high_water_bytes,
        }

    def _grouped_resharded(self, step, target_mesh, spec_by_path,
                           source_axes, target_axes, plan, group):
        """Host-mediated placement, param-group-at-a-time.

        Restores the saved tree to host memory, then places each group
        onto the target mesh via ``make_array_from_callback`` (each
        process contributes its addressable shards — the only
        placement primitive that works in both the gang regime and
        single-process worlds), freeing the host copy as it goes. The
        device-memory accounting models the plan's terms: new shards
        accumulate, and only the IN-FLIGHT group's old/full copy is
        co-resident — the measured high water must stay within the
        plan's whole-tree bound (raises if ever it would not; with
        grouping it sits far below)."""
        import numpy as _np

        import jax
        import orbax.checkpoint as ocp

        from sparkdl_tpu.parallel.sharding import named_sharding_for

        # Restore to HOST numpy via abstract targets from the step's
        # own metadata, never onto the SAVED shardings: the checkpoint
        # records the dead topology's device mesh, and materializing
        # it in the surviving world fails outright when the recorded
        # devices aren't addressable here (the whole reason this path
        # exists). The metadata tree also carries the structure the
        # flat sidecar cannot.
        meta = self._mgr.item_metadata(step)
        target_np = None
        if meta is not None:
            try:
                target_np = jax.tree_util.tree_map(
                    lambda mm: _np.empty(mm.shape, mm.dtype), meta)
            except Exception:  # noqa: BLE001 — metadata shapes are
                target_np = None  # advisory; fall through to raw
        if target_np is not None:
            raw = self._mgr.restore(
                step, args=ocp.args.StandardRestore(target_np))
        else:
            # Degraded: no metadata to build host targets from, so
            # the raw restore materializes the SAVED shardings — fine
            # for numpy/replicated saves, but a tree saved sharded on
            # the dead topology fails here. Surface that typed (NOT
            # as corruption): earlier steps would fail identically
            # and must not be quarantined for it.
            logger.warning(
                "step %d item metadata unavailable under %s — "
                "restoring via the saved shardings", step, self._dir,
            )
            try:
                raw = self._mgr.restore(
                    step, args=ocp.args.StandardRestore())
            except Exception as e:
                raise ReshardRestoreError(
                    f"step {step} under {self._dir} cannot be "
                    "restored in this world: item metadata is "
                    "unavailable and the saved shardings reference "
                    f"the recorded topology ({type(e).__name__}: {e})"
                ) from e
        flat, treedef = jax.tree_util.tree_flatten_with_path(raw)
        n = len(flat)
        group = group if group > 0 else (n or 1)

        def factor(spec_dims, axes):
            f = 1
            for dims in spec_dims or ():
                for name in dims or ():
                    f *= int(axes.get(name, 1))
            return f

        entries = []  # (key, host, nbytes, src_shard, tgt_shard)
        for path, leaf in flat:
            key = jax.tree_util.keystr(path)
            host = _np.asarray(leaf)
            spec = spec_by_path.get(key) or []
            nbytes = int(host.nbytes)
            entries.append((
                key, host, nbytes,
                nbytes // factor(spec, source_axes),
                nbytes // factor(spec, target_axes),
            ))
        del raw, flat
        out = [None] * n
        resident_new = 0
        high_water = 0
        bytes_moved = 0
        groups = 0
        for lo in range(0, n, group):
            batch = range(lo, min(lo + group, n))
            groups += 1
            inflight_src = sum(entries[i][3] for i in batch)
            high_water = max(high_water, resident_new + inflight_src)
            if high_water > plan.restore_high_water_bytes:
                raise ReshardRestoreError(
                    "resharded restore accounting exceeded the plan's "
                    f"high-water bound ({high_water} > "
                    f"{plan.restore_high_water_bytes} bytes) — the "
                    "grouped-restore invariant is broken; file a bug"
                )
            for i in batch:
                key, host, _, _, tgt_shard = entries[i]
                sharding = named_sharding_for(
                    target_mesh, spec_by_path.get(key))
                out[i] = jax.make_array_from_callback(
                    host.shape, sharding,
                    lambda idx, h=host: h[idx],
                )
                resident_new += tgt_shard
                bytes_moved += tgt_shard
                entries[i] = (key, None, 0, 0, 0)  # free the host copy
        return jax.tree_util.tree_unflatten(treedef, out), {
            "mode": "grouped", "params": n, "groups": groups,
            "bytes_moved": int(bytes_moved),
            "high_water_accounted_bytes": int(high_water),
        }

    def close(self):
        """Join any in-flight async save, THEN dispose the manager.

        A train loop's natural shutdown (``finally: ckpt.close()``)
        can land microseconds after an async ``save()`` returned —
        tearing the manager down while its background write is
        mid-flight would abandon a temp dir where a committed step
        should be, and the *final* checkpoint of a run is exactly the
        one a resume needs. ``wait_until_finished`` first makes close
        a commit point. Failures in the join still dispose the
        manager (a wedged writer must not leak it)."""
        if self._mgr_instance is not None:
            try:
                self._mgr_instance.wait_until_finished()
            finally:
                self._mgr_instance.close()
