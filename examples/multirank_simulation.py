"""Checkpoint -> drain -> CROSS-TRANSPORT restore round trip under the
hybrid two-phase-commit — the paper's signature network-agnosticism
scenario on the pluggable transport layer.

Phase A runs an N-rank job over one transport with pipelined ring p2p
(receives lag sends, so messages are ALWAYS in flight at the checkpoint
cut) plus per-row tree allreduces, with one rank straggling while the
checkpoint is pending (watch the coordinator's straggler report name
it, §III-J/K).  The §III-B drain pulls every in-flight byte into
per-rank drain buffers, each rank snapshots its serialized upper half
(comm table, counts, drain buffer), and the launcher writes the
snapshots to a JSON checkpoint IMAGE — transport-free by construction:
membership, counters and hex payloads only, no sockets, no locks.

The phase-A world is then torn down completely and a fresh world is
bootstrapped *from the image file alone* for every `--restore-to`
spec — a different transport, a different WORLD SIZE, or both — through
the one public entrypoint `repro.restore_world(image, plan)`: virtual
comm tables rebound onto new endpoints under the plan's old->new rank
remapping, array shards round-tripped through their logical axes,
drained messages re-delivered on the new network.  Same-size restores
additionally assert ring sequence numbers continue exactly where the
cut happened; every restored world then runs a second traffic epoch
including a SECOND checkpoint, proving the restored world drains and
commits too.

`--chaos` adds seeded rank kills + supervised auto-restart; `--elastic`
is the production autoscaling story: kill 3 of 64 mid-run, resume at 61
from the committed 64-rank image (arrays resharded, protocol state
remapped), lose one more, then grow back to 64 — with the surviving
work bit-identical throughout.

Transports (see `repro.comm.transport`):
  inproc — every rank a thread in one process (reference backend)
  socket — every rank a separate OS process over loopback TCP

    PYTHONPATH=src python examples/multirank_simulation.py \
        [--quick] [--ranks N] [--transport inproc] [--restore-to N@socket]

Defaults: 256 ranks (32 with --quick; MANA_DEMO_RANKS=<n> overrides),
inproc -> inproc.  The CI transport matrix runs inproc -> socket and
socket -> inproc at 64 ranks; the CI elastic arm runs --elastic on both.
"""
import argparse
import json
import os
import random
import sys
import tempfile
import time
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro import RestorePlan, parse_restore_spec, restore_world
from repro.comm.transport import FaultPlan, available_transports
from repro.comm.transport.harness import (row_width, run_world,
                                          run_world_supervised)
from repro.core.codec import DEFAULT_COMPRESS_LEVEL, SnapshotCodec

STEPS_A, STEPS_B, LAG = 10, 6, 2
CKPT_STEP_A, CKPT_STEP_B = 4, 3
# --chaos mode: training horizon, checkpoint cadence, injected kills
CHAOS_STEPS, CHAOS_CKPT_EVERY, CHAOS_KILLS = 24, 6, 3


def build_parser() -> argparse.ArgumentParser:
    """The example's CLI.  The epilog's flag list is GENERATED from the
    parser itself, and the docs CI job (docs/check_docs_drift.py, also
    run by tests/test_docs.py) diffs these flags against the README's
    flag table — so neither the epilog nor the README can silently
    drift from the actual argparse surface again."""
    p = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--quick", action="store_true",
                   help="scale the job down for fast runs")
    p.add_argument("--ranks", type=int, default=None,
                   help="world size (default: 256, or 32 with --quick; "
                        "chaos mode: 64 / 16; MANA_DEMO_RANKS overrides)")
    p.add_argument("--transport", default=None,
                   choices=available_transports(),
                   help="transport the job launches (and is checkpointed) "
                        "under; default inproc")
    p.add_argument("--restore-to", action="append", default=None,
                   metavar="N@TRANSPORT",
                   help="restore spec, repeatable: N@transport, N (same "
                        "transport) or @transport (same world size) — "
                        "each spec restores the phase-A image into a "
                        "fresh world; chaos mode: transports here set "
                        "the restart transport cycle")
    p.add_argument("--image", default=None,
                   help="checkpoint image path (default: a temp file)")
    p.add_argument("--async-ckpt", action="store_true",
                   help="asynchronous checkpoint pipeline: ranks resume "
                        "compute right after staging; a background "
                        "writer ships snapshots and the commit is gated "
                        "on writer acks")
    p.add_argument("--compress-level", type=int,
                   default=DEFAULT_COMPRESS_LEVEL,
                   help="zlib level for binary snapshot containers on "
                        "the --async-ckpt path (default picked by the "
                        "image_codec_throughput benchmark)")
    p.add_argument("--chaos", action="store_true",
                   help="supervised chaos mode: seeded rank kills + "
                        "auto-restart from the last committed image")
    p.add_argument("--elastic", action="store_true",
                   help="elastic chaos (implies --chaos): kill ranks, "
                        "resume at the SURVIVING world size from the "
                        "committed image (arrays resharded, protocol "
                        "state remapped), then grow back to full size")
    p.add_argument("--seed", type=int, default=0,
                   help="chaos fault-schedule seed (reproduces exactly)")
    p.add_argument("--kills", type=int, default=CHAOS_KILLS,
                   help="number of injected rank kills to survive")
    p.add_argument("--log-dir", default=None,
                   help="chaos mode: write attempt records, the failing "
                        "seed and the last image here (CI artifacts)")
    p.add_argument("--store-dir", default=None,
                   help="durable image store root: committed epochs are "
                        "uploaded asynchronously as digest-protected "
                        "manifests; chaos mode then runs the degraded-"
                        "path arms (torn commit, seeded corruption of "
                        "the newest epoch -> fallback restore)")
    p.add_argument("--retain-epochs", type=int, default=2,
                   help="point-in-time restore window: keep the last K "
                        "committed epochs in the launcher collector AND "
                        "the store (default 2)")
    # ---- deprecated spellings (kept working; see resolve_restore_flags)
    p.add_argument("--transport-a", default=None,
                   choices=available_transports(),
                   help="DEPRECATED alias of --transport")
    p.add_argument("--transport-b", default=None,
                   choices=available_transports(),
                   help="DEPRECATED: use --restore-to @TRANSPORT")
    p.add_argument("--flip-transport", action="store_true",
                   help="DEPRECATED: chaos restarts alternate transports; "
                        "use --restore-to @TRANSPORT to name the cycle")
    flags = sorted(s for a in p._actions for s in a.option_strings
                   if s.startswith("--") and s != "--help")
    p.epilog = ("flags: " + " ".join(flags)
                + "\n(documented one-by-one in README.md 'Example flags';"
                  " docs CI diffs that table against this parser)")
    return p


def resolve_restore_flags(args):
    """Collapse the flag surface into (launch transport, restore specs):
    the ONE place the deprecated spellings (--transport-a/--transport-b/
    --flip-transport) are translated into --transport/--restore-to, with
    a notice on stderr.  Each spec is a `(n, transport)` pair from
    `repro.parse_restore_spec`, None meaning "unchanged"."""
    notes = []
    transport = args.transport
    if args.transport_a:
        notes.append("--transport-a is deprecated; use --transport")
        transport = transport or args.transport_a
    transport = transport or "inproc"
    specs = [parse_restore_spec(s) for s in (args.restore_to or [])]
    if args.transport_b:
        notes.append("--transport-b is deprecated; use "
                     "--restore-to @TRANSPORT")
        specs.append((None, args.transport_b))
    if args.flip_transport:
        notes.append("--flip-transport is deprecated; use "
                     "--restore-to @TRANSPORT to name the restart cycle")
        if not any(t for _, t in specs):
            specs.append((None, "inproc"))
    for note in notes:
        print(f"DEPRECATED: {note}", file=sys.stderr)
    if not specs:
        specs = [(None, None)]   # same size, same transport
    return transport, specs


def parse_args(argv=None):
    args = build_parser().parse_args(argv)
    if args.elastic:
        args.chaos = True
    if args.ranks is None:
        if args.chaos:
            args.ranks = int(os.environ.get("MANA_DEMO_RANKS",
                                            "16" if args.quick else "64"))
        else:
            args.ranks = int(os.environ.get("MANA_DEMO_RANKS",
                                            "32" if args.quick else "256"))
    return args


def payload(src, seq):
    return src.to_bytes(2, "big") + seq.to_bytes(4, "big")


# ---------------------------------------------------------------------------
# phase A: run under the launch transport, checkpoint mid-traffic, write
# the image
# ---------------------------------------------------------------------------

def make_phase_a(n):
    row_w = row_width(n)
    straggler = min(7, n - 1)

    def work(ctx):
        a, r = ctx.agent, ctx.rank
        base = (r // row_w) * row_w
        a.row = a.create_comm(range(base, base + row_w))
        snap_box = {}

        def snapshot():
            # the app's comm-handle bindings (world/row vids) are
            # upper-half state: vids survive restore by design, and
            # membership alone cannot distinguish identically-membered
            # comms (a row of width n IS the world)
            snap_box.setdefault("snap", {
                "step": step, "recvd": recvd,
                "world_comm": a.world_comm, "row": a.row,
                "agent": a.serialize()})

        recvd = 0
        step = 0
        for step in range(STEPS_A):
            if r == 0 and step == CKPT_STEP_A:
                print(f">>> A: checkpoint requested (step {step})")
                ctx.coord.request_checkpoint()
            if r == straggler and step == CKPT_STEP_A and a._ckpt_pending():
                time.sleep(0.3)  # straggler inside the ckpt window
            a.send((r + 1) % n, payload(r, step), tag=0)
            if step >= LAG:   # pipelined ring: receives lag sends
                m = a.recv((r - 1) % n, timeout=120)
                assert payload((r - 1) % n, recvd) == m.payload
                recvd += 1
            a.allreduce(a.row, 1, lambda x, y: x + y)
            if a.safe_point(snapshot) and r == 0:
                print(f">>> A: checkpoint committed (step {step})")
        # end of the finite demo loop — a real job would keep stepping.
        # The world barrier orders every rank after the checkpoint
        # request, then ranks service safe points until the pending
        # epoch resolves (the LAG in-flight messages per ring pair are
        # deliberately NOT consumed: they are the §III-B drain's
        # payload at the cut).
        a.barrier_op(a.world_comm)
        while a._ckpt_pending():
            if a.safe_point(snapshot) and r == 0:
                print(">>> A: checkpoint committed")
            time.sleep(0.002)
        return snap_box["snap"]

    return work


def watch_stragglers(server):
    time.sleep(0.45)
    report = server.straggler_report(threshold=0.2)
    if report:
        sample = dict(list(report.items())[:3])
        print(f">>> A: straggler report while waiting: {len(report)} "
              f"rank(s) not at a safe point yet, e.g. {sample}")


def phase_a(n, transport, image_path, async_ckpt=False):
    res = run_world(transport, n, make_phase_a(n), unblock_window=0.5,
                    timeout=300, async_ckpt=async_ckpt,
                    on_running=watch_stragglers)
    assert len(res.results) == n and res.coord_stats["checkpoints"] == 1
    drained = sum(len(s["agent"]["drain_buffer"])
                  for s in res.results.values())
    assert drained > 0, "expected in-flight messages at the cut"
    image = {"transport": transport, "n_ranks": n,
             "ranks": {str(r): s for r, s in res.results.items()}}
    with open(image_path, "w") as f:
        json.dump(image, f)
    print(f">>> A: {n} ranks snapshotted over {transport!r}; {drained} "
          f"messages were drained in flight; coordinator stats: "
          f"{res.coord_stats}")
    print(f">>> A: checkpoint image written: {image_path} "
          f"({os.path.getsize(image_path)} bytes, transport-free JSON)")


# ---------------------------------------------------------------------------
# phase B: bootstrap a fresh world from the image alone — any transport,
# any world size, all through repro.restore_world
# ---------------------------------------------------------------------------

def make_phase_b(rw, from_transport, to_transport):
    identity = rw.plan.is_identity

    def work(ctx):
        a, r, ep, n = ctx.agent, ctx.rank, ctx.ep, ctx.n
        prev = (r - 1) % n
        # §III-C restore through the ONE entrypoint: rebind the (plan-
        # remapped) virtual comm table onto THIS world's endpoint,
        # re-register gids, restore collective counts, re-append drained
        # messages for replay.
        owned = rw.bind(ctx)
        if identity:
            st = owned[r]
            assert st["agent"]["transport"] == from_transport
            # App-held comm HANDLES come from the image (vids are stable
            # across restore); membership can't distinguish identically-
            # membered comms, e.g. a row as wide as the world.
            a.world_comm = st["world_comm"]
            a.row = st["row"]
            # replay the backlog out of the drain buffer: sequence
            # numbers must continue exactly at the cut (closure check:
            # predecessor's sends minus our receives at ITS cut step)
            backlog = len(ep.drain_buffer)
            expected = (rw.state(prev)["step"] + 1) - st["recvd"]
            assert backlog == expected, (r, backlog, expected)
            seq = st["recvd"]
            for _ in range(backlog):
                m = a.recv(prev, timeout=120)
                assert m.payload == payload(prev, seq), (r, seq)
                seq += 1
        else:
            # ELASTIC restore: the old ring's sequence numbers are
            # meaningless under the new numbering — replay exactly the
            # remapped in-flight backlog the bind re-appended, then
            # rebuild the topology comms for the NEW world (the plan's
            # docstring: rows/rings are app topology, the app re-derives
            # them; the world comm was remapped in place)
            for src, _dst, tag, _ in rw.drains_for(r):
                a.recv(src, tag=tag, timeout=120)
            row_w = row_width(n)
            base = (r // row_w) * row_w
            a.row = a.create_comm(range(base, base + row_w))
        assert len(ep.drain_buffer) == 0
        # fresh epoch on a new tag, with a second checkpoint
        recvd = 0
        step = 0
        for step in range(STEPS_B):
            if r == 0 and step == CKPT_STEP_B:
                print(f">>> B: second checkpoint requested (step {step})")
                ctx.coord.request_checkpoint()
            a.send((r + 1) % n, payload(r, step), tag=1)
            if step >= 1:
                m = a.recv(prev, tag=1, timeout=120)
                assert m.payload == payload(prev, recvd)
                recvd += 1
            a.allreduce(a.row, 1, lambda x, y: x + y)
            if a.safe_point(lambda: None) and r == 0:
                print(f">>> B: second checkpoint committed (step {step})")
        a.barrier_op(a.world_comm)
        while a._ckpt_pending():  # end-of-job safe-point service
            if a.safe_point(lambda: None) and r == 0:
                print(">>> B: second checkpoint committed")
            time.sleep(0.002)
        # pipeline tail (lag 1) — possibly replayed from the second
        # checkpoint's drain buffer
        a.recv(prev, tag=1, timeout=120)
        assert a.transport == to_transport
        return {"sent": list(ep.sent_bytes), "recvd": list(ep.recvd_bytes)}

    return work


def phase_b(n_to, transport, image_path, async_ckpt=False):
    with open(image_path) as f:
        image = json.load(f)
    n_from = image["n_ranks"]
    rw = restore_world(image,
                       RestorePlan.between(n_from, n_to, transport))
    rw.states()   # decode once, launcher-side (socket children fork)
    print(f">>> B: restoring image written under {image['transport']!r} "
          f"at {n_from} ranks onto a fresh {transport!r} world of {n_to}")
    res = run_world(transport, n_to,
                    make_phase_b(rw, image["transport"], transport),
                    unblock_window=0.5, timeout=300, async_ckpt=async_ckpt)
    assert len(res.results) == n_to and res.coord_stats["checkpoints"] == 1
    if rw.plan.is_identity:
        # §III-B closure in the RESTORED world: every ring pair's byte
        # counters balance once the traffic of phase B is fully consumed
        # (checked from the per-rank counter vectors each rank shipped
        # back — the launcher holds no endpoint in a multi-process world)
        for r in range(n_to):
            for s in ((r - 1) % n_to, (r + 1) % n_to):
                assert (res.results[r]["recvd"][s]
                        == res.results[s]["sent"][r]), (r, s)
    print(f">>> B: world restored over {transport!r} at {n_to} ranks "
          f"committed a second checkpoint; coordinator stats: "
          f"{res.coord_stats}")


# ---------------------------------------------------------------------------
# --chaos: seeded rank kills + supervised auto-restart from the last
# committed image (the NERSC-production reliability scenario)
# ---------------------------------------------------------------------------

def snap_state(blob):
    """A chaos snapshot's app state, whichever way it shipped: the
    sync path sends plain JSON-safe dicts, the --async-ckpt path packs
    the same dict into a binary snapshot container's compressed extra
    cell (`SnapshotCodec.encode(..., extra=...)`)."""
    if isinstance(blob, (bytes, bytearray)):
        return SnapshotCodec().decode_extra(blob)
    return blob


def make_chaos_worker(n, image, target, ckpt_every, async_ckpt=False,
                      compress_level=DEFAULT_COMPRESS_LEVEL):
    """One incarnation of the chaos training job: a pipelined ring
    (receives lag sends, so messages are ALWAYS in flight) plus per-row
    allreduces, checkpointing every `ckpt_every` steps.  Each commit
    ships the rank's snapshot to the launcher-side image collector —
    the snapshot must NOT live in rank memory, because a killed rank's
    memory is gone.  With `image`, the incarnation resumes from the
    cut: comms rebound, drained messages re-delivered, and every
    receive asserts the ring sequence continues exactly where the cut
    happened."""
    row_w = row_width(n)
    rw = None if image is None else restore_world(image)
    if rw is not None:
        rw.states()   # decode once before the fork

    def work(ctx):
        a, r = ctx.agent, ctx.rank
        prev = (r - 1) % n
        if rw is None:
            start = recvd = 0
            base = (r // row_w) * row_w
            a.row = a.create_comm(range(base, base + row_w))
        else:
            blob = rw.bind(ctx)[r]
            a.world_comm = blob["world_comm"]
            a.row = blob["row"]
            start, recvd = blob["step"] + 1, blob["recvd"]
        step = start

        def snapshot():
            # captured at the cut under the ADOPTED epoch; JSON-safe
            payload = {"step": step, "recvd": recvd,
                       "world_comm": a.world_comm, "row": a.row,
                       "agent": a.serialize()}
            if async_ckpt:
                # async pipeline: stage only — the background writer
                # encodes the binary container (the serialized agent,
                # drain payloads included, deflates well) and ships it
                epoch = a.ckpt_epoch
                codec = SnapshotCodec(compress_level=compress_level)
                return lambda: codec.encode(epoch, {}, extra=payload)
            ctx.coord.ship_snapshot(a.ckpt_epoch, payload)

        for step in range(start, target):
            # cadence checkpoints, plus an early post-restart one (a
            # fresh incarnation re-establishes its recovery point
            # immediately instead of waiting out the cadence)
            if r == 0 and step and (step % ckpt_every == 0
                                    or step == start + 1):
                ctx.coord.request_checkpoint()
            a.send((r + 1) % n, payload(r, step), tag=0)
            while recvd <= step - LAG:
                m = a.recv(prev, timeout=120)
                assert m.payload == payload(prev, recvd), (r, recvd)
                recvd += 1
            a.allreduce(a.row, 1, lambda x, y: x + y)
            # sample intent ONCE and gate the park on the same sample:
            # the fault hook observes `pending` strictly before any park
            # under it, so a when_pending kill deterministically fires
            # on a rank that has seen checkpoint intent but not yet
            # parked — phase 1 is open by construction (closure needs
            # this rank parked)
            pending = a._ckpt_pending()
            if ctx.faults is not None:
                ctx.faults.on_step(r, step, ckpt_pending=pending)
            if pending:
                a.safe_point(snapshot)
        a.barrier_op(a.world_comm)
        while a._ckpt_pending():
            if ctx.faults is not None:
                ctx.faults.on_step(r, step, ckpt_pending=True)
            a.safe_point(snapshot)
            time.sleep(0.002)
        while recvd < target:  # pipeline tail (and any replayed drain)
            m = a.recv(prev, timeout=120)
            assert m.payload == payload(prev, recvd), (r, recvd)
            recvd += 1
        return {"start": start, "step": target, "recvd": recvd}

    return work


def chaos_schedule(seed, n, kills, target):
    """The seeded fault schedule: attempt i < kills injects one rank
    kill (attempt 1 is the mid-phase-1 variant: the victim dies after
    observing checkpoint intent but before parking, while a straggler
    in another row deterministically holds phase 1 open); later
    attempts run fault-free.  Reproduces exactly from (seed, n,
    kills)."""
    row_w = row_width(n)
    plans = {}
    for attempt in range(kills):
        rng = random.Random(f"{seed}:{attempt}")
        plan = FaultPlan(seed)
        victim = rng.randrange(n)
        if attempt == 1 and kills > 1:
            straggler = ((victim + row_w) % n if n > row_w
                         else (victim + 1) % n)
            plan.kill(victim, at_step=0, when_pending=True)
            plan.straggle(straggler, at_step=0, seconds=0.7,
                          when_pending=True)
            plans[attempt] = (plan, victim, "mid-phase-1")
        else:
            step = rng.randrange(2, target - 2)
            plan.kill(victim, at_step=step)
            plans[attempt] = (plan, victim, f"step {step}")
    return plans


def open_chaos_store(args):
    """The durable tier behind --store-dir (None without the flag)."""
    if not args.store_dir:
        return None
    from repro.core.image_store import open_store
    return open_store(args.store_dir, retain=args.retain_epochs)


def run_store_arms(args, transports, n_restart, fn_factory, check):
    """The degraded-path arms behind --store-dir, run AFTER the chaos
    horizon so the store holds real committed epochs:

    arm 1 (torn commit): a seeded `StoreCrash` kills the "launcher"
    between blob upload and manifest commit — the manifest-last
    protocol leaves NO visible epoch, so the restart simply ignores
    the torn upload.

    arm 2 (scrub -> fallback): a seeded single-bit flip corrupts the
    newest epoch's blobs on disk; a COLD restart (launcher RAM gone,
    image=None) falls back a generation with a typed
    `EpochFallbackWarning` and still finishes the horizon."""
    from repro.core.image_store import (EpochFallbackWarning, StoreCrash,
                                        StoreFaults, open_store)
    sd, retain = args.store_dir, args.retain_epochs
    store = open_store(sd, retain=retain)
    eps = store.epochs()
    assert len(eps) >= 2, f"need >=2 retained epochs for fallback, got {eps}"

    # --- arm 1: launcher dies between upload and manifest commit -----
    torn = open_store(sd, retain=retain,
                      faults=StoreFaults(args.seed).crash_before_manifest())
    fake = dict(store.load(eps[-1]), epoch=eps[-1] + 1000)
    try:
        torn.commit(fake)
        raise AssertionError("crash_before_manifest never fired")
    except StoreCrash:
        pass
    assert open_store(sd, retain=retain).epochs() == eps, \
        "torn commit must be invisible (manifest-last protocol)"
    print(f">>> store arm 1: torn commit (crash before manifest) left "
          f"epochs {eps} unchanged")

    # --- arm 2: corrupt newest epoch, cold-restart from the store ----
    man = store.manifest(eps[-1])
    rng = random.Random(f"{args.seed}:store-flip")
    for rec in man["blobs"].values():
        path = os.path.join(sd, rec["key"])
        raw = bytearray(open(path, "rb").read())
        raw[rng.randrange(len(raw))] ^= 1 << rng.randrange(8)
        with open(path, "wb") as f:
            f.write(bytes(raw))
    cold = open_store(sd, retain=retain)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        sup = run_world_supervised(
            transports, n_restart, fn_factory, max_restarts=0,
            store=cold, retain_epochs=retain, unblock_window=0.5,
            timeout=300, async_ckpt=args.async_ckpt)
    cold.stop()
    assert any(issubclass(w.category, EpochFallbackWarning)
               for w in caught), [w.category for w in caught]
    assert sup.image is not None and sup.image["epoch"] == eps[-2], \
        (sup.image and sup.image["epoch"], eps)
    check(sup)
    print(f">>> store arm 2: newest epoch {eps[-1]} corrupted (seeded "
          f"bit flips) -> cold restart fell back to epoch {eps[-2]} "
          f"with EpochFallbackWarning and finished the horizon")


def chaos_main(args, transport, specs):
    n, seed, kills = args.ranks, args.seed, args.kills
    target, every = CHAOS_STEPS, CHAOS_CKPT_EVERY
    transports = [transport] + [t for _, t in specs if t]
    schedule = chaos_schedule(seed, n, kills, target)
    resume_steps = []   # min resume step per attempt (0 = cold start)

    def fn_factory(attempt, image):
        resume = (0 if image is None else 1 + min(
            int(snap_state(b)["step"]) for b in image["ranks"].values()))
        resume_steps.append(resume)
        what = (f"kill rank {schedule[attempt][1]} at "
                f"{schedule[attempt][2]}" if attempt in schedule
                else "no faults")
        print(f">>> chaos attempt {attempt}: resume step {resume} "
              f"(image epoch {image['epoch'] if image else None}), "
              f"{what}")
        return make_chaos_worker(n, image, target, every,
                                 async_ckpt=args.async_ckpt,
                                 compress_level=args.compress_level)

    t0 = time.perf_counter()
    store = open_chaos_store(args)
    print(f"=== {n}-rank CHAOS run: seed {seed}, {kills} injected kills, "
          f"checkpoint every {every} steps, transport(s) {transports}, "
          f"{'async' if args.async_ckpt else 'sync'} checkpoints"
          + (f", store {args.store_dir} (retain "
             f"{args.retain_epochs})" if store else "") + " ===")
    sup = run_world_supervised(
        transports, n, fn_factory, max_restarts=kills + 2,
        faults_for_attempt=lambda a: schedule.get(a, (None,))[0],
        unblock_window=0.5, timeout=300, log_dir=args.log_dir,
        store=store, retain_epochs=args.retain_epochs,
        async_ckpt=args.async_ckpt)

    # every rank finished the horizon with the ring sequence intact
    assert len(sup.result.results) == n
    assert all(v["step"] == target and v["recvd"] == target
               for v in sup.result.results.values())
    assert len(sup.failures) == kills, sup.failures
    # bounded lost work: after a kill at step K, the next incarnation
    # resumes within at most 2 checkpoint intervals of K (the committed
    # interval plus the epoch that was in flight at the failure)
    for f in sup.failures:
        attempt = f["attempt"]
        plan, victim, what = schedule[attempt]
        assert f["failed_ranks"] == [victim], f
        if what.startswith("step"):
            fired = max(int(what.split()[1]), resume_steps[attempt])
            lost = fired - resume_steps[attempt + 1]
            assert lost <= 2 * every + 2, (f, fired, resume_steps)
    assert all(a <= b for a, b in zip(resume_steps, resume_steps[1:])), \
        resume_steps  # progress is monotone: restarts never lose ground
    recoveries = [f.get("recovery_s") for f in sup.failures]
    print(f">>> chaos: survived {kills} kills in {sup.attempts} attempts; "
          f"resume steps {resume_steps}; recovery latencies "
          f"{[round(x, 3) for x in recoveries if x is not None]}s")
    if store is not None:
        store.stop()
        print(f">>> store: retained epochs {store.epochs()}")

        def arms_factory(attempt, image):
            assert image is not None, "cold restart must adopt a store epoch"
            resume = 1 + min(int(snap_state(b)["step"])
                             for b in image["ranks"].values())
            print(f">>> store cold restart: resume step {resume} "
                  f"(image epoch {image['epoch']})")
            return make_chaos_worker(n, image, target, every,
                                     async_ckpt=args.async_ckpt,
                                     compress_level=args.compress_level)

        def check(sup2):
            assert len(sup2.result.results) == n
            assert all(v["step"] == target and v["recvd"] == target
                       for v in sup2.result.results.values())

        run_store_arms(args, transports, n, arms_factory, check)
    print(f"PASS ({time.perf_counter() - t0:.1f}s)")


# ---------------------------------------------------------------------------
# --elastic: the autoscaling chaos scenario — shrink to the survivors,
# grow back when capacity returns, bit-identical logical state throughout
# ---------------------------------------------------------------------------

def make_elastic_worker(G, rw, shards, start, target, ckpt_every,
                        async_ckpt=False,
                        compress_level=DEFAULT_COMPRESS_LEVEL):
    """One incarnation of the ELASTIC chaos job.  The logical state is a
    global float64 vector x = arange(G) + step (logical axis "batch",
    sharded across whatever world size this attempt got) plus a
    replicated step counter; per step the job runs a lagged ring p2p
    (messages ALWAYS in flight at a cut), one world allreduce (count
    equalization pins every rank to the same step at a committed cut —
    what makes an elastic resume point well-defined), then x += 1.
    On restore each rank asserts its resharded slice is BIT-IDENTICAL
    to the logical arange — across shrink, grow, and both transports."""

    def work(ctx):
        a, r, n = ctx.agent, ctx.rank, ctx.n
        prev = (r - 1) % n
        if rw is None:
            x = np.array_split(np.arange(G, dtype=np.float64), n)[r].copy()
            rep = np.zeros((), np.float64)
        else:
            rw.bind(ctx)   # remapped comms/counts/drains (cold: seeded)
            x = shards[r]["x"].copy()
            rep = shards[r]["rep"].copy().reshape(())
            # the tentpole promise, checked where it matters: the
            # reshard is exact, not approximate
            want = np.array_split(
                np.arange(G, dtype=np.float64) + start, n)[r]
            assert np.array_equal(x, want), (r, n, start)
            assert float(rep) == float(start), (r, rep, start)
            # replay the remapped in-flight backlog; old-world sequence
            # numbers are meaningless under the new numbering, so just
            # consume — at a committed cut this completes every message
            # <= the cut step, and fresh traffic restarts at `start`
            # uniformly across ALL pairs (old and new alike)
            for src, _dst, tag, _ in rw.drains_for(r):
                a.recv(src, tag=tag, timeout=120)
        assert len(ctx.ep.drain_buffer) == 0
        recvd = start
        step = start

        def snapshot():
            epoch = a.ckpt_epoch
            codec = SnapshotCodec(compress_level=compress_level)
            arrays = {"x": x.copy(), "rep": rep.copy()}
            extra = {"step": step, "recvd": recvd,
                     "logical": {"x": ["batch"], "rep": []},
                     "agent": a.serialize()}
            if async_ckpt:
                return lambda: codec.encode(epoch, arrays, extra=extra)
            ctx.coord.ship_snapshot(epoch,
                                    codec.encode(epoch, arrays, extra=extra))

        for step in range(start, target):
            if r == 0 and step and (step % ckpt_every == 0
                                    or step == start + 1):
                ctx.coord.request_checkpoint()
            a.send((r + 1) % n, payload(r, step), tag=0)
            while recvd <= step - LAG:
                m = a.recv(prev, timeout=120)
                assert m.payload == payload(prev, recvd), (r, recvd)
                recvd += 1
            a.allreduce(a.world_comm, 1.0, lambda p, q: p + q)
            x += 1.0
            rep += 1.0
            pending = a._ckpt_pending()
            if ctx.faults is not None:
                ctx.faults.on_step(r, step, ckpt_pending=pending)
            if pending:
                a.safe_point(snapshot)
        a.barrier_op(a.world_comm)
        while a._ckpt_pending():
            if ctx.faults is not None:
                ctx.faults.on_step(r, step, ckpt_pending=True)
            a.safe_point(snapshot)
            time.sleep(0.002)
        while recvd < target:  # pipeline tail
            m = a.recv(prev, timeout=120)
            assert m.payload == payload(prev, recvd), (r, recvd)
            recvd += 1
        return {"start": start, "x": x.tolist(), "rep": float(rep)}

    return work


def elastic_main(args, transport, specs):
    n0, seed, kills = args.ranks, args.seed, args.kills
    n1 = n0 - kills
    assert n1 >= 1, f"--kills {kills} leaves no survivors of {n0}"
    target, every = CHAOS_STEPS, CHAOS_CKPT_EVERY
    G = 2 * n0
    transports = [transport] + [t for _, t in specs if t]
    # the seeded schedule: attempt 0 at n0 loses `kills` ranks at once
    # (strictly after the first cadence commit), attempt 1 runs at the
    # surviving n1 and loses one more, attempt 2 grows back to n0 when
    # capacity "returns" and finishes the horizon fault-free
    rng = random.Random(f"{seed}:elastic")
    step0 = every + 2
    plan0 = FaultPlan(seed)
    victims0 = sorted(rng.sample(range(n0), kills))
    for v in victims0:
        plan0.kill(v, at_step=step0)
    plan1 = FaultPlan(seed)
    victim1 = rng.randrange(n1)
    plan1.kill(victim1, at_step=min(step0 + every, target - 2))
    schedule = {0: plan0, 1: plan1}
    capacities = {0: n0, 1: n1, 2: n0}

    sizes, origins, resume_steps = [], [], []

    def fn_factory(attempt, image):
        if image is None:
            rw, shards, resume = None, None, 0
        else:
            rw = restore_world(image)
            steps = {st["step"] for st in rw.states().values()}
            # counts-equalized commit => ONE global step at the cut
            assert len(steps) == 1, steps
            resume = steps.pop() + 1
            shards = rw.reshard()   # launcher-side; forked children share
        sizes.append(None if rw is None else rw.plan.n_to)
        origins.append(None if image is None else int(image["n_ranks"]))
        resume_steps.append(resume)
        print(f">>> elastic attempt {attempt}: "
              f"{'cold start' if rw is None else f'{rw.plan.n_from} -> {rw.plan.n_to} ranks'}"
              f", resume step {resume}")
        return make_elastic_worker(G, rw, shards, resume, target, every,
                                   async_ckpt=args.async_ckpt,
                                   compress_level=args.compress_level)

    t0 = time.perf_counter()
    store = open_chaos_store(args)
    print(f"=== ELASTIC chaos: {n0} ranks, kill {kills} -> resume at "
          f"{n1} -> grow back to {n0}; seed {seed}, transport(s) "
          f"{transports}"
          + (f", store {args.store_dir} (retain "
             f"{args.retain_epochs})" if store else "") + " ===")
    sup = run_world_supervised(
        transports, n0, fn_factory, max_restarts=4, elastic=True,
        faults_for_attempt=lambda a: schedule.get(a),
        capacity_for_attempt=lambda a, rf: capacities.get(a),
        unblock_window=0.5, timeout=300, log_dir=args.log_dir,
        store=store, retain_epochs=args.retain_epochs,
        async_ckpt=args.async_ckpt)

    assert sup.final_n == n0 and len(sup.result.results) == n0
    assert [f["n"] for f in sup.failures] == [n0, n1], sup.failures
    assert sizes[1] == n1 and sizes[2] == n0, sizes
    # the grow-back attempt restored a COMMITTED image of the shrunken
    # world — progress made at n1 survived the growth
    assert origins[2] == n1, origins
    assert resume_steps[2] >= resume_steps[1] > 0, resume_steps
    # bit-identical logical state on the surviving work: the final
    # shards concatenate to exactly arange(G) + target, every rank's
    # replicated counter agrees, and the ring sequence closed
    full = np.concatenate([np.asarray(sup.result.results[r]["x"])
                           for r in range(n0)])
    assert np.array_equal(full,
                          np.arange(G, dtype=np.float64) + target)
    assert all(v["rep"] == float(target)
               for v in sup.result.results.values())
    recoveries = [round(f["recovery_s"], 3) for f in sup.failures
                  if f.get("recovery_s") is not None]
    print(f">>> elastic: {n0} -> {n1} -> {n0} ranks in {sup.attempts} "
          f"attempts; resume steps {resume_steps}; recovery latencies "
          f"{recoveries}s; final state bit-identical to the logical "
          f"arange + {target}")
    if store is not None:
        store.stop()
        print(f">>> store: retained epochs {store.epochs()}")

        # SHRINK-elastic fallback: the cold restart adopts the store
        # epoch at whatever world size committed it and reshards down
        # to the surviving n1 — the same fn_factory handles it
        def check(sup2):
            assert sup2.final_n == n1 and len(sup2.result.results) == n1
            full = np.concatenate([np.asarray(sup2.result.results[r]["x"])
                                   for r in range(n1)])
            assert np.array_equal(full,
                                  np.arange(G, dtype=np.float64) + target)

        run_store_arms(args, transports, n1, fn_factory, check)
    print(f"PASS ({time.perf_counter() - t0:.1f}s)")


def main():
    args = parse_args()
    transport, specs = resolve_restore_flags(args)
    if args.chaos:
        try:
            if args.elastic:
                elastic_main(args, transport, specs)
            else:
                chaos_main(args, transport, specs)
        except BaseException:
            if args.log_dir:
                os.makedirs(args.log_dir, exist_ok=True)
                repro = (f"python examples/multirank_simulation.py "
                         f"--chaos --ranks {args.ranks} "
                         f"--seed {args.seed} --kills {args.kills} "
                         f"--transport {transport}"
                         + "".join(f" --restore-to {n or ''}@{t}"
                                   for n, t in specs if t)
                         + (" --elastic" if args.elastic else "")
                         + (f" --store-dir {args.store_dir}"
                            if args.store_dir else "")
                         + (" --quick" if args.quick else ""))
                with open(os.path.join(args.log_dir,
                                       "failing_seed.txt"), "w") as f:
                    f.write(f"seed={args.seed}\nrepro: {repro}\n")
            raise
        return
    n = args.ranks
    image_path = args.image or os.path.join(
        tempfile.mkdtemp(prefix="mana_image_"), "ckpt_image.json")
    t0 = time.perf_counter()
    restores = [(spec_n or n, spec_t or transport)
                for spec_n, spec_t in specs]
    print(f"=== {n}-rank checkpoint -> drain -> restore round trip "
          f"(rows of {row_width(n)}, tree collectives, "
          f"{transport} -> {', '.join(f'{rn}@{rt}' for rn, rt in restores)}, "
          f"{'async' if args.async_ckpt else 'sync'} checkpoints) ===")
    phase_a(n, transport, image_path, args.async_ckpt)
    for n_to, t_to in restores:
        phase_b(n_to, t_to, image_path, args.async_ckpt)
    print(f"PASS ({time.perf_counter() - t0:.1f}s)")


if __name__ == "__main__":
    main()
