#!/usr/bin/env python3
"""Does the system still start on the chip? The quickest proof.

Drives the main path once, through the entry point a user calls — the
elastic launcher running ``examples/llama_train.py`` fed by the shm
data plane — at the full width of ``llama_1b`` on one TPU chip:

  probe    a child opens the chip, says what it is and lets go again;
           the native shm ring is built (needs ``g++``)
  train    a fresh start: a few steps at 3 x 2048, one durable save
  resume   a crash injected one step after a save; the agent restarts
           the worker in place on the same chip, it restores,
           finishes, and its train step comes out of the compile cache

With ``--chips 4`` it runs, instead and only, the ``fsdp`` step over
four chips and what it is compared with: the same seed and tokens on
one of the chips, accumulated to the same global batch.

This script never initialises JAX: the worker under the launcher needs
the chip, and a chip belongs to one process. What the device was, it
learns from the file the worker wrote. It prints what it found before
it judges it; every phase ends in one JSON line; the last line is the
verdict. Sizes can be given as arguments to rehearse the control flow
on the CPU at ``llama_tiny`` — the device check is never waived, so
such a rehearsal ends in ``"ok": false``.
"""

import argparse
import importlib.metadata
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import traceback
import uuid

HERE = os.path.dirname(os.path.abspath(__file__))
#: the small files (logs, the workers' reports); the chip tool brings
#: this directory back
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
#: every process this script starts carries it, so that every one can
#: be found again and stopped, whoever its parent has become
MARK = ("CHIP_SMOKE_RUN", uuid.uuid4().hex)
#: |loss of the four-chip step - loss of the accumulated one-chip
#: step|, every step: the two differ in summation order (bf16
#: all-reduce vs f32 accumulation) and in nothing else
LOSS_TOLERANCE = 0.05
#: |first loss - ln(vocab)| of a randomly initialised model
FIRST_LOSS_TOLERANCE = 1.5

_PROBE = """
import json, subprocess
out = {}
try:
    from dlrover_tpu.data import shm_ring
    shm_ring._load_library()
    out["shm_ring"] = "built"
except subprocess.CalledProcessError as e:
    out["shm_ring_error"] = (e.stderr or b"").decode()[-2000:]
except Exception as e:
    out["shm_ring_error"] = repr(e)
import jax
devices = jax.devices()
out.update(platform=devices[0].platform,
           device_kind=devices[0].device_kind, count=len(devices))
print("PROBE " + json.dumps(out))
"""


def say(**fields):
    print(json.dumps(fields), flush=True)


def tail(path, n=60, width=400):
    try:
        with open(path, errors="replace") as f:
            return "".join(
                ln if len(ln) <= width else ln[:width] + "...\n"
                for ln in f.readlines()[-n:]
            )
    except OSError as e:
        return f"<{path}: {e}>"


def child_env(**extra):
    env = dict(os.environ, **extra)
    env[MARK[0]] = MARK[1]
    env["PYTHONPATH"] = os.pathsep.join(
        [HERE] + [p for p in [env.get("PYTHONPATH")] if p]
    )
    return env


def stop_everything_started():
    """SIGKILL whatever still carries this run's mark."""
    needle = f"{MARK[0]}={MARK[1]}".encode()
    for name in os.listdir("/proc"):
        if not name.isdigit() or int(name) == os.getpid():
            continue
        try:
            with open(f"/proc/{name}/environ", "rb") as f:
                if needle in f.read().split(b"\0"):
                    os.kill(int(name), signal.SIGKILL)
        except (OSError, ValueError):
            continue


def versions():
    found = {"python": sys.version.split()[0]}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            found[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            found[pkg] = None
    return found


def read_reports(path):
    try:
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]
    except OSError:
        return []


#: what failed, phase by phase; empty at the end means ok
FAILURES = []


def phase(name):
    """Run one phase: one JSON line of what it saw, and when it failed
    the logs' tail before it. A failure is recorded and the next phase
    still runs: a run that fails says all it can."""

    def wrap(fn):
        def run(*args, **kw):
            t0 = time.time()
            saw, problems, logs = {}, [], []
            try:
                fn(saw, problems, logs, *args, **kw)
            except Exception:
                problems.append("exception in chip_smoke.py")
                print(traceback.format_exc(), flush=True)
            ok = not problems
            if not ok:
                for log in logs:
                    print(f"----- last lines of {log}", flush=True)
                    print(tail(log), flush=True)
            say(phase=name, ok=ok, seconds=round(time.time() - t0, 1),
                problems=problems, saw=saw)
            if not ok:
                FAILURES.append(f"{name}: {'; '.join(problems)}")
            return saw

        return run

    return wrap


@phase("probe")
def probe(saw, problems, logs):
    log = os.path.join(OUT_DIR, "probe.log")
    logs.append(log)
    with open(log, "w") as f:
        rc = subprocess.run(
            [sys.executable, "-c", _PROBE], cwd=HERE, env=child_env(),
            stdout=f, stderr=subprocess.STDOUT, timeout=120,
        ).returncode
    saw["rc"] = rc
    for line in tail(log, 400, 10**6).splitlines():
        if line.startswith("PROBE "):
            saw.update(json.loads(line[len("PROBE "):]))
    if rc != 0 or "platform" not in saw:
        problems.append(f"the probe child exited {rc} with no device")
    if "shm_ring_error" in saw:
        problems.append("the native shm ring did not build")


def launch(name, example_args, logs, timeout, max_restarts=0, **env):
    """The launcher a user runs, as a module of the copied tree (no
    console script: nothing here depends on an installation)."""
    log = os.path.join(OUT_DIR, f"{name}.log")
    report = os.path.join(OUT_DIR, f"{name}.report.jsonl")
    logs.append(log)
    if os.path.exists(report):
        os.remove(report)
    cmd = [
        sys.executable, "-m", "dlrover_tpu.trainer.elastic_run",
        "--standalone", "--nnodes", "1:1",
        "--max_restarts", str(max_restarts), "--monitor_interval", "1",
        os.path.join(HERE, "examples", "llama_train.py"), "--",
        *[str(a) for a in example_args], "--report", report,
    ]
    with open(log, "w") as f:
        proc = subprocess.Popen(
            cmd, cwd=HERE, env=child_env(**env), stdout=f,
            stderr=subprocess.STDOUT,
        )
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            rc = f"no end after {timeout}s"
        finally:
            stop_everything_started()
            proc.wait()
    return rc, read_reports(report), log


def check_worker(saw, problems, rc, reports, first, steps):
    """What every launcher run has to show. ``first`` is the
    incarnation whose first step is judged (the last one)."""
    saw["launcher_rc"] = rc
    if rc != 0:
        problems.append(f"launcher exited {rc}")
    if first is None:
        problems.append("the worker reported no first step")
        return
    for key in ("platform", "device_kind", "device_count", "model",
                "batch", "accum", "seq", "mesh", "kernel_in_step",
                "tuning", "compile_cache_dir", "collectives"):
        saw[key] = first.get(key)
    if first["platform"] != "tpu":
        problems.append(f"platform {first['platform']}")
    if not first["kernel_in_step"]:
        problems.append("no tpu_custom_call in the compiled step")
    blocks = first["tuning"] or {}
    if None in (blocks.get("block_q"), blocks.get("block_k")):
        problems.append(f"no attention blocks recorded: {blocks!r}")
    final = [r for r in reports if r["event"] == "final"]
    if not final:
        problems.append("the worker reported no end")
        return
    losses = final[-1]["losses"]
    saw["losses"] = losses
    saw["peak_bytes_in_use"] = final[-1]["peak_bytes_in_use"]
    if final[-1]["step"] != steps:
        problems.append(f"ended at step {final[-1]['step']}")
    if not losses or not all(math.isfinite(v) for _, v in losses):
        problems.append("a loss is not finite")


def first_steps(reports):
    return [r for r in reports if r["event"] == "first_step"]


def size_args(args, ckpt_dir, **over):
    sized = dict(
        model=args.model, batch_size=args.batch, seq_len=args.seq,
        num_workers=args.num_workers, ckpt_dir=ckpt_dir,
    )
    sized.update(over)
    return [
        x for k, v in sized.items()
        for x in ("--" + k.replace("_", "-"), v)
    ]


@phase("train")
def train(saw, problems, logs, args, ckpt_dir):
    rc, reports, _ = launch(
        "train",
        size_args(args, ckpt_dir, steps=args.steps),
        logs, timeout=args.phase_timeout,
    )
    firsts = first_steps(reports)
    first = firsts[-1] if firsts else None
    check_worker(saw, problems, rc, reports, first, args.steps)
    if first is None or "losses" not in saw:
        return
    saw["first_step_secs"] = first["first_step_secs"]
    saw["step_compile_secs"] = first["step_compile_secs"]
    saw["step_cache"] = [
        first["step_cache_hits"], first["step_cache_requests"]
    ]
    want = math.log(first["vocab_size"])
    saw["ln_vocab"] = round(want, 3)
    if abs(saw["losses"][0][1] - want) > FIRST_LOSS_TOLERANCE:
        problems.append(
            f"first loss {saw['losses'][0][1]} is not near {want:.3f}"
        )


@phase("resume")
def resume(saw, problems, logs, args, ckpt_dir):
    """The example saves every 10 steps and at the end; crash at 11:
    the second incarnation starts from 10 on the chip the first one
    died on, and ends at 12."""
    rc, reports, log = launch(
        "resume",
        size_args(args, ckpt_dir, steps=12),
        logs, max_restarts=1, timeout=args.phase_timeout,
        DLROVER_FAULT_INJECT="crash@11",
    )
    firsts = first_steps(reports)
    second = firsts[-1] if len(firsts) == 2 else None
    check_worker(saw, problems, rc, reports, second, 12)
    text = tail(log, 10**6, 10**6)
    saw["injected"] = "INJECTED CRASH" in text
    saw["restored_line"] = next(
        (ln for ln in text.splitlines()
         if ln.startswith("RESTORED from step")), None,
    )
    saw["first_step_secs"] = [r["first_step_secs"] for r in firsts]
    saw["step_cache"] = [
        [r["step_cache_hits"], r["step_cache_requests"]]
        for r in firsts
    ]
    if not saw["injected"]:
        problems.append("no crash was injected")
    if len(firsts) != 2:
        problems.append(f"{len(firsts)} incarnations reported, not 2")
        return
    if (second["restart_count"], second["start_step"]) != (1, 10):
        problems.append(
            f"restart {second['restart_count']} started at step "
            f"{second['start_step']}, not restart 1 at step 10"
        )
    if saw["restored_line"] != "RESTORED from step 10":
        problems.append("no 'RESTORED from step 10' line")
    hits, requests = saw["step_cache"][1]
    if requests < 1 or hits != requests:
        problems.append(
            f"the restarted worker's train step was compiled, not "
            f"read from the cache ({hits} hits of {requests})"
        )


@phase("accum_one_chip")
def accum_one_chip(saw, problems, logs, args, ckpt_dir):
    rc, reports, _ = launch(
        "accum_one_chip",
        size_args(
            args, ckpt_dir, steps=args.steps,
            batch_size=args.global_batch,
            accum_steps=args.global_batch, num_devices=1,
            num_workers=1,
        ),
        logs, timeout=args.phase_timeout,
    )
    firsts = first_steps(reports)
    check_worker(
        saw, problems, rc, reports, firsts[-1] if firsts else None,
        args.steps,
    )


@phase("fsdp_four_chips")
def fsdp_four_chips(saw, problems, logs, args, ckpt_dir, reference):
    rc, reports, _ = launch(
        "fsdp_four_chips",
        size_args(
            args, ckpt_dir, steps=args.steps,
            batch_size=args.global_batch, num_workers=1,
        ),
        logs, timeout=args.phase_timeout,
    )
    firsts = first_steps(reports)
    first = firsts[-1] if firsts else None
    check_worker(saw, problems, rc, reports, first, args.steps)
    if first is None or "losses" not in saw:
        return
    saw["param_bytes_by_device"] = first["param_bytes_by_device"]
    saw["param_bytes_total"] = first["param_bytes_total"]
    held = first["param_bytes_by_device"].values()
    if first["device_count"] != 4 or len(held) != 4:
        problems.append(
            f"{first['device_count']} devices, parameters on "
            f"{len(held)}"
        )
    elif max(held) > 0.5 * first["param_bytes_total"]:
        problems.append("the parameters are not spread over the chips")
    if not sum(first["collectives"].values()):
        problems.append("no collective in the four-chip step")
    saw["reference_losses"] = reference
    diffs = [
        abs(a[1] - b[1]) for a, b in zip(saw["losses"], reference)
    ]
    saw["max_loss_difference"] = max(diffs) if diffs else None
    saw["loss_tolerance"] = LOSS_TOLERANCE
    if len(diffs) != args.steps or max(diffs) > LOSS_TOLERANCE:
        problems.append(
            f"losses differ from the one-chip accumulated run by "
            f"{saw['max_loss_difference']} (tolerance "
            f"{LOSS_TOLERANCE})"
        )


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--model", default="llama_1b")
    ap.add_argument("--batch", type=int, default=3)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--global-batch", type=int, default=8,
                    help="--chips 4: sequences per optimizer step")
    ap.add_argument("--num-workers", type=int, default=2,
                    help="coworker processes feeding the shm ring")
    ap.add_argument("--phase-timeout", type=int, default=450)
    args = ap.parse_args()

    os.makedirs(OUT_DIR, exist_ok=True)
    say(versions=versions(), gxx=shutil.which("g++"))
    say(env={
        k: v for k, v in sorted(os.environ.items())
        if k in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")
        or k.startswith("TPU_")
    })
    device = None
    # a 6.6 GB state: outside the checkout, outside what is copied back
    ckpt_root = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        from dlrover_tpu.common.cachedir import resolve_cache_dir

        say(compile_cache_dir=resolve_cache_dir())
        seen = probe()
        if seen.get("platform"):
            device = {
                "platform": seen["platform"],
                "kind": seen["device_kind"], "count": seen["count"],
            }
        if seen.get("platform") != "tpu" and args.model == "llama_1b":
            # a rehearsal names a small model and runs everything
            FAILURES.append(
                f"platform {seen.get('platform')}: no accelerator, "
                "and llama_1b is not run without one"
            )
        elif args.chips == 4:
            ref = accum_one_chip(args, os.path.join(ckpt_root, "a"))
            shutil.rmtree(ckpt_root, ignore_errors=True)
            seen = fsdp_four_chips(
                args, os.path.join(ckpt_root, "b"),
                ref.get("losses", []),
            )
        else:
            train(args, os.path.join(ckpt_root, "train"))
            shutil.rmtree(ckpt_root, ignore_errors=True)
            seen = resume(args, os.path.join(ckpt_root, "resume"))
        if seen.get("device_count"):  # what the last worker saw
            device = {
                "platform": seen["platform"],
                "kind": seen["device_kind"],
                "count": seen["device_count"],
            }
        if not FAILURES and device["count"] != args.chips:
            FAILURES.append(
                f"{device['count']} devices where --chips "
                f"{args.chips} was asked"
            )
    except Exception:
        FAILURES.append(traceback.format_exc())
        print(FAILURES[-1], flush=True)
    finally:
        stop_everything_started()
        shutil.rmtree(ckpt_root, ignore_errors=True)
    if FAILURES:
        say(ok=False, device=device, reason=" | ".join(FAILURES))
        return 1
    say(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
