"""``consolidate-and-reshard-ckpts`` console tool.

Mirrors the reference CLI surface (setup.py:36-40 console script ->
utils/consolidate_and_reshard_ckpts.py argparse main): point it at a
sharded checkpoint, get a consolidated copy or a copy resharded for a
new parallel layout.

Operator additions for elastic resume (docs/resilience.md):

- ``inspect``: print the schema manifest (mesh axes/sizes, process
  count, step, per-leaf shapes/dtypes) of a checkpoint — or of every
  marked step in a CheckpointManager directory — so compatibility can
  be judged BEFORE burning a restore attempt on a pod.
- ``--dry-run``: for consolidate/reshard, print what would be read and
  written (and the schema diff against the target layout) without
  touching anything.

SDC triage (docs/resilience.md "SDC defense"):

- ``replay``: print the per-leaf content digests (order-independent
  XOR fold + wraparound sum of the raw bits, plus a value sum) of a
  committed checkpoint step, so two copies of the same step — on two
  pods, or before/after a transfer — can be diffed leaf-by-leaf
  offline.  The full in-situ step replay (re-executing the training
  step and printing the *gradient* digests) is
  ``Trainer.fit(replay_step=N)``, which needs the model; this command
  needs only the checkpoint.

Fleet operations (docs/resilience.md "Host replacement & grow-back"):

- ``supervise``: run the jax-free supervisor daemon (launch, sense,
  decide, restart — and with ``--replace``, provision replacement
  hosts / grow a shrunk pod back).
- ``fleet-history``: print a supervised run's quarantine/replacement
  timeline from the daemon's event journal, jax-free.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _load_schema(ckpt_dir: str):
    """Schema manifest for ``ckpt_dir``: the ``_MANIFEST`` inside a
    manager step dir, the ``<dir>.schema.json`` sidecar of a standalone
    save, or None."""
    from torchacc_tpu.checkpoint.io import MANIFEST, _schema_sidecar

    manifest = os.path.join(ckpt_dir, MANIFEST)
    if os.path.exists(manifest):
        try:
            with open(manifest) as f:
                m = json.load(f)
            return m.get("schema") or {"tree": m.get("tree")}
        except (OSError, ValueError):
            return None
    sidecar = _schema_sidecar(os.path.abspath(ckpt_dir))
    if os.path.exists(sidecar):
        try:
            with open(sidecar) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None
    return None


def _schema_from_metadata(ckpt_dir: str):
    """Fallback for checkpoints predating schema manifests: leaf
    shapes/dtypes from orbax tree metadata (no mesh/process info — that
    was never recorded)."""
    import orbax.checkpoint as ocp

    from torchacc_tpu.checkpoint.schema import state_schema

    meta = ocp.StandardCheckpointer().metadata(os.path.abspath(ckpt_dir))
    meta = getattr(meta, "item_metadata", meta)
    # installed orbax wraps the tree in a metadata pytree node
    meta = getattr(meta, "tree", meta)
    schema = state_schema(meta)
    # orbax metadata carries neither live shardings nor the writing
    # pod's size — report "unknown", never the inspecting process's own
    schema["mesh"] = None
    schema["process_count"] = None
    return schema


def _print_schema(label: str, schema, *, leaves: bool, out=None):
    out = out if out is not None else sys.stdout  # resolved at call time
    mesh = schema.get("mesh")
    tree = schema.get("tree") or {}
    print(f"{label}:", file=out)
    print(f"  mesh: "
          + (" ".join(f"{k}={v}" for k, v in mesh.items()) if mesh
             else "<not recorded>"), file=out)
    if schema.get("process_count") is not None:
        print(f"  processes: {schema['process_count']}", file=out)
    print(f"  leaves: {tree.get('leaves', '?')}  "
          f"digest: {str(tree.get('digest', '?'))[:16]}", file=out)
    specs = schema.get("leaf_specs") or {}
    if leaves and specs:
        for path in sorted(specs):
            s = specs[path]
            print(f"    {path}: {tuple(s['shape'])} {s['dtype']}",
                  file=out)


def _print_tiers(d: str, steps, mirror: str) -> None:
    """Per-tier state of a tiered checkpoint dir (docs/resilience.md
    "Tiered checkpointing"): which steps are durable locally (tier 1)
    vs mirrored (tier 2), plus the writer's advisory trickle progress
    (``_TIERED`` — submitted / verdict watermark / RAM snapshots).

    Tier 2 is the object-store mirror: a step counts as committed only
    under its two-phase ``_COMMIT`` marker, and every committed step is
    verified payload-by-payload (``verify_commit``) so torn uploads
    (payload bytes, no marker) and checksum-mismatched objects are
    flagged explicitly instead of masquerading as restorable."""
    from torchacc_tpu.checkpoint.tiered import read_tiered_status
    from torchacc_tpu.store import (
        LocalObjectStore,
        commit_marker_key,
        list_commits,
        verify_commit,
    )

    t2_state: dict = {}
    if mirror and os.path.isdir(mirror):
        store = LocalObjectStore(mirror)
        # the ONE notion of "commit-marked step" the restore path uses
        marked = {int(p) for p in list_commits(store) if p.isdigit()}
        for step in marked:
            problems = verify_commit(store, str(step))
            t2_state[step] = ("committed" if not problems
                              else "CORRUPT (" + "; ".join(problems) + ")")
        # payload bytes without a marker: a torn upload the restore
        # path will never offer — name it so the operator knows why
        for name in os.listdir(mirror):
            if (name.isdigit() and int(name) not in marked
                    and os.path.isdir(os.path.join(mirror, name))
                    and not store.exists(commit_marker_key(name))):
                t2_state[int(name)] = "TORN (no commit marker)"
    print("tiers:")
    for step in sorted(set(steps) | set(t2_state)):
        t1 = "committed" if step in set(steps) else "missing"
        t2 = t2_state.get(step, "missing") if mirror else "-"
        print(f"  step {step}: tier1={t1} tier2={t2}")
    status = read_tiered_status(d)
    if status is not None:
        print(f"  trickle: submitted={status.get('submitted')} "
              f"verdicts_through={status.get('verdicts_through')} "
              f"durable={status.get('durable')} "
              f"tier0_ram={status.get('tier0_steps')}")


def _cmd_inspect(args) -> int:
    from torchacc_tpu.checkpoint.io import MANIFEST

    d = args.ckpt_dir
    if not os.path.isdir(d):
        print(f"error: {d} is not a directory", file=sys.stderr)
        return 2
    # a CheckpointManager directory: numeric step subdirs with markers
    steps = sorted(
        int(n) for n in os.listdir(d)
        if n.isdigit() and os.path.exists(os.path.join(d, n, MANIFEST)))
    if steps:
        for step in steps:
            try:
                with open(os.path.join(d, str(step), MANIFEST)) as f:
                    manifest = json.load(f)
            except (OSError, ValueError) as e:
                # a truncated/corrupt marker is exactly what an operator
                # points this tool at — report it, keep printing siblings
                print(f"step {step}: unreadable {MANIFEST} ({e})",
                      file=sys.stderr)
                continue
            schema = manifest.get("schema") or {"tree": manifest.get("tree")}
            _print_schema(f"step {step}", schema, leaves=args.leaves)
        _print_tiers(d, steps, args.mirror)
        return 0
    schema = _load_schema(d)
    if schema is None:
        try:
            schema = _schema_from_metadata(d)
        except Exception as e:  # noqa: BLE001 - operator-facing tool
            print(f"error: no schema manifest and orbax metadata "
                  f"unreadable for {d}: {e!r}", file=sys.stderr)
            return 2
    _print_schema(d, schema, leaves=args.leaves)
    return 0


def _cmd_replay(args) -> int:
    from torchacc_tpu.checkpoint.io import MANIFEST

    d = args.ckpt_dir
    if not os.path.isdir(d):
        print(f"error: {d} is not a directory", file=sys.stderr)
        return 2
    step = args.step
    if step is None:
        # manager dir: newest marked step; else digest the dir itself
        marked = sorted(
            int(n) for n in os.listdir(d)
            if n.isdigit() and os.path.exists(os.path.join(d, n, MANIFEST)))
        if marked:
            step = marked[-1]
    if step is not None:
        step_dir = os.path.join(d, str(step))
        if not os.path.isdir(step_dir):
            print(f"error: no step {step} under {d}", file=sys.stderr)
            return 2
        item = os.path.join(step_dir, "default")
        d = item if os.path.isdir(item) else step_dir
    import jax
    import orbax.checkpoint as ocp

    from torchacc_tpu.resilience.sdc import host_digests

    try:
        ckptr = ocp.StandardCheckpointer()
        # restore via a sharding-free abstract tree from the metadata:
        # digesting must work on ANY machine (that is the point of the
        # tool), not just one with the writing pod's device count
        meta = ckptr.metadata(os.path.abspath(d))
        meta = getattr(meta, "item_metadata", meta)
        # installed orbax wraps the tree in a metadata pytree node
        meta = getattr(meta, "tree", meta)
        dev = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype,
                                           sharding=dev), meta)
        tree = ckptr.restore(os.path.abspath(d), abstract)
    except Exception as e:  # noqa: BLE001 - operator-facing tool
        print(f"error: cannot restore {d}: {e!r}", file=sys.stderr)
        return 2
    digs = host_digests(tree)
    if args.json:
        json.dump({"path": os.path.abspath(d), "step": step,
                   "digests": digs}, sys.stdout, indent=1)
        print()
        return 0
    label = f"{args.ckpt_dir}" + (f" step {step}" if step is not None else "")
    print(f"digests of {label} ({len(digs)} leaves):")
    for path in sorted(digs):
        s = digs[path]
        print(f"  {path}: xor={s['bits_xor']} sum={s['bits_sum']} "
              f"value_sum={s['f32_sum']:.6g} "
              f"{tuple(s['shape'])} {s['dtype']}")
    return 0


def _cmd_fleet_history(args) -> int:
    """The quarantine/replacement timeline of a supervised run — the
    daemon's decision/provision/grow-back event journal plus the
    current quarantine file, rendered oldest-first.  Deliberately
    jax-free (filename literals match supervisor/daemon.py
    EVENTS_FILE / QUARANTINE_FILE)."""
    events_path = os.path.join(args.run_dir, "supervisor_events.jsonl")
    quarantine_path = os.path.join(args.run_dir, "sdc_quarantine.json")
    events = []
    try:
        with open(events_path, "rb") as f:
            for line in f.read().splitlines():
                if not line.strip():
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    events.append(rec)
    except OSError:
        pass
    quarantine = {}
    try:
        with open(quarantine_path) as f:
            q = json.load(f)
        if isinstance(q, dict):
            quarantine = q
    except (OSError, ValueError):
        pass
    if args.json:
        print(json.dumps({"run_dir": args.run_dir, "events": events,
                          "quarantine": quarantine}, indent=2,
                         sort_keys=True))
        return 0
    if not events and not quarantine:
        print(f"no fleet history under {args.run_dir} (no "
              f"supervisor_events.jsonl, no quarantine file)")
        return 0
    print(f"fleet history of {args.run_dir} ({len(events)} event(s)):")
    for rec in events:
        t = rec.get("time")
        try:
            import datetime
            stamp = datetime.datetime.fromtimestamp(
                float(t)).strftime("%H:%M:%S") if t else "--:--:--"
        except (TypeError, ValueError, OverflowError):
            stamp = "--:--:--"
        inc = rec.get("incarnation", "?")
        kind = rec.get("event", "?")
        detail = {k: v for k, v in rec.items()
                  if k not in ("time", "incarnation", "event")}
        body = " ".join(f"{k}={v}" for k, v in sorted(detail.items()))
        print(f"  {stamp} inc={inc:<3} {kind:<18} {body}")
    if quarantine:
        print(f"quarantined now ({len(quarantine)} host(s)):")
        for h in sorted(quarantine, key=str):
            info = quarantine[h]
            body = (" ".join(f"{k}={v}" for k, v in sorted(info.items()))
                    if isinstance(info, dict) else str(info))
            print(f"  host {h}: {body}")
    else:
        print("quarantined now: none")
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "replay":
        p = argparse.ArgumentParser(
            prog="consolidate_and_reshard_ckpts replay",
            description="Print per-leaf content digests of a committed "
                        "checkpoint step (offline SDC triage; compare "
                        "two copies leaf-by-leaf).")
        p.add_argument("ckpt_dir",
                       help="checkpoint (or manager) directory")
        p.add_argument("--step", type=int, default=None,
                       help="manager step to digest (default: newest "
                            "marked step)")
        p.add_argument("--json", action="store_true",
                       help="machine-readable output for diffing")
        return _cmd_replay(p.parse_args(argv[1:]))
    if argv and argv[0] == "supervise":
        p = argparse.ArgumentParser(
            prog="consolidate_and_reshard_ckpts supervise",
            description="Run the supervisor daemon: launch + monitor "
                        "training workers, sense failure (exit "
                        "disposition / healthz probes / flight "
                        "bundles), and apply the restart policy "
                        "(docs/resilience.md 'Supervisor').  Worker "
                        "argv follows '--'; placeholders {host} "
                        "{world} {incarnation} {run_dir} {coord_port} "
                        "{obs_port} are substituted per launch.  "
                        "Exit code: 0 run completed, 3 terminal "
                        "give-up (see flight_giveup.json).")
        p.add_argument("--run-dir", required=True,
                       help="shared run directory (checkpoints, "
                            "quarantine file, flight bundles)")
        p.add_argument("--world", type=int, default=1,
                       help="initial worker count (one process per "
                            "host on the local fixture)")
        p.add_argument("--max-restarts", type=int, default=8,
                       help="total restart budget (preemption resumes "
                            "are free); exhausted -> give up")
        p.add_argument("--backoff-initial-s", type=float, default=1.0)
        p.add_argument("--backoff-max-s", type=float, default=60.0)
        p.add_argument("--backoff-jitter", type=float, default=0.25)
        p.add_argument("--min-world", type=int, default=1,
                       help="never shrink the pod below this many "
                            "hosts — give up instead")
        p.add_argument("--probe", action="store_true",
                       help="poll each worker's /healthz (workers "
                            "must serve it on the {obs_port} passed "
                            "to them)")
        p.add_argument("--incarnation-timeout-s", type=float,
                       default=None,
                       help="kill + restart an incarnation older than "
                            "this (last-resort hang detector)")
        p.add_argument("--exit-grace-s", type=float, default=15.0,
                       help="window for peer workers to follow a "
                            "failed one out before SIGTERM")
        p.add_argument("--obs-port", type=int, default=None,
                       help="serve the supervisor's own /metrics "
                            "(supervisor_* counters) here")
        p.add_argument("--obs-port-base", type=int, default=None,
                       help="stable worker telemetry ports: host i "
                            "serves on base+i every incarnation (a "
                            "fronting serve router's static worker "
                            "registry)")
        p.add_argument("--router-url", default=None,
                       help="a fronting serve router (serve/router.py) "
                            "to scrape under host -1 and notify on "
                            "planned stops (/drain)")
        p.add_argument("--replace", action="store_true",
                       help="answer crash/SDC host loss by "
                            "PROVISIONING a replacement (budget-"
                            "bounded) before falling back to "
                            "exclude+shrink, and grow excluded slots "
                            "back when capacity allows "
                            "(docs/resilience.md 'Host replacement & "
                            "grow-back')")
        p.add_argument("--replace-budget", type=int, default=2,
                       help="total replacement/grow-back attempts "
                            "charged across the run")
        p.add_argument("--no-grow-back", action="store_true",
                       help="replace failed hosts but never re-expand "
                            "a previously shrunk pod")
        p.add_argument("--provisioner", default="local",
                       choices=("local", "gke", "ray"),
                       help="where replacement capacity comes from "
                            "(gke/ray are typed stubs)")
        p.add_argument("--spares", type=int, default=0,
                       help="pre-warm this many hot-spare hosts at "
                            "startup (SparePool)")
        p.add_argument("--provision-capacity", type=int, default=None,
                       help="local provisioner: total grants before "
                            "capacity exhaustion (default unbounded)")
        p.add_argument("--provision-delay-s", type=float, default=0.0,
                       help="local provisioner: simulated cold "
                            "acquisition latency")
        p.add_argument("--env", action="append", default=[],
                       metavar="KEY=VALUE",
                       help="extra worker environment (repeatable; "
                            "values may use the same placeholders)")
        if "--" not in argv:
            print("error: worker argv required after '--'",
                  file=sys.stderr)
            return 2
        split = argv.index("--")
        args = p.parse_args(argv[1:split])
        args.worker_argv = argv[split + 1:]
        if not args.worker_argv:
            print("error: worker argv required after '--'",
                  file=sys.stderr)
            return 2
        # deliberately jax-free: the daemon must run on a host that
        # never initialises a device backend
        from torchacc_tpu.supervisor.daemon import main_from_args
        return main_from_args(args)
    if argv and argv[0] == "fleet-history":
        p = argparse.ArgumentParser(
            prog="consolidate_and_reshard_ckpts fleet-history",
            description="Print the quarantine/replacement timeline of "
                        "a supervised run: the daemon's event journal "
                        "(decisions, provision attempts, grow-backs, "
                        "quarantine clears) plus the current "
                        "quarantine file.  Pure filesystem, jax-free.")
        p.add_argument("run_dir", help="the supervisor --run-dir")
        p.add_argument("--json", action="store_true",
                       help="machine-readable output")
        return _cmd_fleet_history(p.parse_args(argv[1:]))
    if argv and argv[0] == "inspect":
        p = argparse.ArgumentParser(
            prog="consolidate_and_reshard_ckpts inspect",
            description="Print a checkpoint's schema manifest (mesh, "
                        "step, leaf shapes/dtypes).")
        p.add_argument("ckpt_dir", help="checkpoint (or manager) directory")
        p.add_argument("--leaves", action="store_true",
                       help="also list per-leaf shapes/dtypes")
        p.add_argument("--mirror", default=None,
                       help="tier-2 mirror directory: the per-step tier "
                            "table shows which steps are durable "
                            "locally vs mirrored (tiered checkpointing, "
                            "docs/resilience.md)")
        return _cmd_inspect(p.parse_args(argv[1:]))

    p = argparse.ArgumentParser(
        prog="consolidate_and_reshard_ckpts",
        description="Consolidate or reshard torchacc_tpu checkpoints "
                    "('inspect <dir>' prints the schema manifest).")
    p.add_argument("--ckpt_dir", required=True, help="source checkpoint")
    p.add_argument("--save_dir", required=True, help="destination")
    p.add_argument("--reshard_num", type=int, default=1,
                   help="target fsdp shard count (1 = consolidate only)")
    p.add_argument("--mesh_axis", default="fsdp",
                   help="mesh axis to reshard over (default fsdp)")
    p.add_argument("--dry-run", action="store_true", dest="dry_run",
                   help="print the plan (and the schema diff for "
                        "reshard) without reading arrays or writing")
    args = p.parse_args(argv)

    import jax

    from torchacc_tpu.checkpoint.reshard import (
        consolidate_checkpoint,
        reshard_checkpoint,
    )

    if args.reshard_num <= 1:
        if args.dry_run:
            schema = _load_schema(args.ckpt_dir)
            if schema is None:
                try:
                    schema = _schema_from_metadata(args.ckpt_dir)
                except Exception as e:  # noqa: BLE001
                    print(f"error: cannot read {args.ckpt_dir}: {e!r}",
                          file=sys.stderr)
                    return 2
            _print_schema(f"would consolidate {args.ckpt_dir} -> "
                          f"{args.save_dir}", schema, leaves=False)
            return 0
        consolidate_checkpoint(args.ckpt_dir, args.save_dir)
        return 0

    import numpy as np
    import orbax.checkpoint as ocp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devs = jax.devices()
    if len(devs) < args.reshard_num:
        print(f"error: {args.reshard_num} shards requested but only "
              f"{len(devs)} devices available (set "
              f"XLA_FLAGS=--xla_force_host_platform_device_count=N "
              "JAX_PLATFORMS=cpu to reshard offline)", file=sys.stderr)
        return 2
    mesh = Mesh(np.asarray(devs[:args.reshard_num]), (args.mesh_axis,))

    # shapes/dtypes from checkpoint metadata — no full host read
    # (manager item dirs return the tree directly; standalone dirs wrap
    # it in a metadata object)
    meta = ocp.StandardCheckpointer().metadata(os.path.abspath(args.ckpt_dir))
    meta = getattr(meta, "item_metadata", meta)
    # installed orbax wraps the tree in a metadata pytree node
    meta = getattr(meta, "tree", meta)

    def absify(x):
        shape = tuple(x.shape)
        spec = PartitionSpec()
        if len(shape) >= 1 and shape[0] % args.reshard_num == 0 and shape[0]:
            spec = PartitionSpec(args.mesh_axis)
        return jax.ShapeDtypeStruct(shape, x.dtype,
                                    sharding=NamedSharding(mesh, spec))

    abstract = jax.tree.map(absify, meta)
    if args.dry_run:
        from torchacc_tpu.checkpoint.schema import schema_diff, state_schema

        target = state_schema(abstract)
        _print_schema(f"would reshard {args.ckpt_dir} -> {args.save_dir}",
                      target, leaves=False)
        saved = _load_schema(args.ckpt_dir)
        if saved is not None:
            diff = schema_diff(saved, target)
            print("  changes vs source:"
                  + ("".join(f"\n    {d}" for d in diff) if diff
                     else " none"))
        # the layout-pair plan the transfer engine would compile: per-
        # leaf src→dst spec diff + bytes moved (the offline source
        # layout is the host-restored tree, so src reads 'host')
        from torchacc_tpu.parallel.transfer import format_plan, transfer_plan

        src_abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype), meta)
        print(format_plan(transfer_plan(src_abstract, abstract),
                          max_rows=64))
        return 0
    reshard_checkpoint(args.ckpt_dir, args.save_dir, abstract)
    return 0


if __name__ == "__main__":
    sys.exit(main())
