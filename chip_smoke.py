#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the main path still starts
on the chip.

Drives train -> export -> serve through the commands a user runs, at
the full width of the dense LM every LM bench config uses (d_model
1024, 8 layers, 16 heads, d_ff 4096, vocab 32000; random weights from
a seed), then compiles every Pallas kernel that path can select and
compares each with its in-tree reference:

  device   one child asks jax what it sees; anything but a TPU backend
           ends the run non-zero before any work
  train    ``python -m kubeflow_tpu.examples.lm`` (the TpuJob pod's
           command): a few optimizer steps at seq 2048, ``--export``
  serve    ``python -m kubeflow_tpu.serving.server`` (the serving pod's
           command, manifest defaults: 8 decode slots, 4 steps per
           sync) over that export: concurrent ``:generate`` requests
           across several prefill buckets, greedy and sampled; /metrics
           must show they went through the DecodeEngine; SIGTERM must
           end the server
  kernels  one child, in process, ``interpret=False`` asserted: flash
           fwd + fused bwd at the table tiles for seq 8192 (numerics vs
           ``reference_attention``, then ``bench_longcontext`` steps),
           the kv_len-masked bidirectional flash path at seq 512, the
           paged decode kernel vs the gather path at engine shapes,
           the fused sampler vs ``sample_logits``, the engine's batch
           prefill program vs its row prefill on the smoke's prompts
           (agreement to rounding: bf16 and float32); on more than one
           chip, also that params, optimizer state, the engine's KV
           cache and the kernels themselves are spread over the mesh

One process per chip: this parent never touches a jax backend and runs
its children strictly one after another, so each opens the chip the
previous one released (the kernels child runs after the server's
SIGTERM for exactly that reason). Any failed phase exits non-zero
naming the phase. Width is never cut to fit the time limit; steps,
requests and sequence length are.

The last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK_DIR = os.path.join(ROOT, ".chip_smoke")  # git-ignored scratch
LOG_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
MODEL_NAME = "transformer"
# stated tolerance of every kernel-vs-reference comparison: max |a - b|
# over max |reference|, bf16 inputs with f32 accumulation on both sides
KERNEL_REL_TOL = 3e-2
# the same comparison with float32 activations at ``highest`` matmul
# precision: what is left between two programs is summation order
F32_REL_TOL = 1e-4


@dataclasses.dataclass(frozen=True)
class Sizes:
    # the dense LM of bench_longcontext / bench_decode / the engine
    # bench (kubeflow_tpu/bench/suite.py) — never cut
    d_model: int = 1024
    n_layers: int = 8
    n_heads: int = 16
    d_ff: int = 4096
    vocab: int = 32000
    # cut to fit the time limit
    seq_len: int = 2048
    train_steps: int = 4
    per_device_batch: int = 4
    prompt_lens: Tuple[int, ...] = (5, 20, 70, 200, 600)
    max_new: int = 16
    # kernel checks
    flash_seq: int = 8192
    flash_heads: int = 2
    longcontext_steps: int = 2
    masked_seq: int = 512
    masked_batch: int = 16
    masked_heads: int = 12
    page_size: int = 64
    slots: int = 8
    check_layers: int = 2

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


FULL = Sizes()


class PhaseError(RuntimeError):
    """A phase failed; ``main`` turns it into a non-zero exit naming it."""

    def __init__(self, phase: str, message: str) -> None:
        super().__init__(f"phase {phase}: {message}")
        self.phase = phase


def require(cond: bool, phase: str, message: str) -> None:
    if not cond:
        raise PhaseError(phase, message)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ---------------------------------------------------------------------------
# parent side: children, one at a time
# ---------------------------------------------------------------------------


def child_env(extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra or {})
    return env


def run_child(phase: str, argv: List[str], *, timeout: float,
              env: Optional[Dict[str, str]] = None,
              ok_codes: Tuple[int, ...] = (0,)) -> str:
    """Run one child to completion; its stdout is returned, both
    streams are kept under LOG_DIR. An exit code outside ``ok_codes``
    or a timeout fails the phase (``subprocess.run`` kills the child on
    timeout, so the chip is free for whatever runs next)."""
    os.makedirs(LOG_DIR, exist_ok=True)
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            [sys.executable, *argv], cwd=ROOT, env=child_env(env),
            capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired as e:
        raise PhaseError(phase, f"timed out after {timeout:.0f}s; stderr "
                         f"tail:\n{_tail(e.stderr)}") from None
    with open(os.path.join(LOG_DIR, f"{phase}.out"), "w") as f:
        f.write(proc.stdout)
    with open(os.path.join(LOG_DIR, f"{phase}.err"), "w") as f:
        f.write(proc.stderr)
    if proc.returncode not in ok_codes:
        raise PhaseError(phase, f"exit code {proc.returncode}; stderr "
                         f"tail:\n{_tail(proc.stderr)}")
    say(f"{phase}: child done in {time.monotonic() - t0:.1f}s")
    return proc.stdout


def _tail(text: Any, n: int = 3000) -> str:
    if isinstance(text, bytes):
        text = text.decode(errors="replace")
    return (text or "")[-n:]


def last_json_line(phase: str, stdout: str) -> Dict[str, Any]:
    for line in reversed(stdout.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise PhaseError(phase, "child printed no JSON result")


def phase_device() -> Dict[str, Any]:
    argv = [os.path.abspath(__file__), "--phase", "device"]
    info = last_json_line("device", run_child("device", argv, timeout=300))
    require(not info["interpret"], "device",
            "resolve_interpret(None) is True: Pallas kernels would run "
            "interpreted")
    return info


def phase_train(sizes: Sizes, work_dir: str, *, dp: int = 1
                ) -> Dict[str, Any]:
    export_dir = os.path.join(work_dir, "models", MODEL_NAME)
    argv = ["-m", "kubeflow_tpu.examples.lm",
            "--steps", str(sizes.train_steps),
            "--per-device-batch", str(sizes.per_device_batch),
            "--seq-len", str(sizes.seq_len),
            "--vocab-size", str(sizes.vocab),
            "--d-model", str(sizes.d_model),
            "--n-layers", str(sizes.n_layers),
            "--n-heads", str(sizes.n_heads),
            "--d-ff", str(sizes.d_ff),
            "--log-every", "1", "--export", export_dir]
    out = run_child("train", argv, timeout=900)
    rows = [json.loads(l) for l in out.splitlines() if l.startswith("{")]
    steps = [r for r in rows if "loss" in r]
    require(len(steps) == sizes.train_steps, "train",
            f"{len(steps)} logged steps, wanted {sizes.train_steps}")
    losses = [float(r["loss"]) for r in steps]
    require(all(math.isfinite(x) for x in losses), "train",
            f"non-finite loss in {losses}")
    # a random-init LM over a uniform random stream sits near ln(vocab)
    require(all(0.0 < x < 3.0 * math.log(sizes.vocab) for x in losses),
            "train", f"implausible loss in {losses}")
    exported = [r["exported"] for r in rows if "exported" in r]
    require(len(exported) == 1, "train", "no export line")
    for name in ("model.yaml", "params.npz"):
        require(os.path.isfile(os.path.join(exported[0], name)), "train",
                f"export is missing {name}")
    tokens_per_step = sizes.per_device_batch * dp * sizes.seq_len
    # each step's line is printed after its loss was read back, so the
    # gap between two lines is one whole step on the device (the first
    # line comes after the compile; every gap is steady state)
    gaps = sorted(b["ts"] - a["ts"] for a, b in zip(steps, steps[1:]))
    step_s = gaps[len(gaps) // 2] if gaps else None
    summary = {
        "steps": len(steps),
        "first_loss": round(losses[0], 4),
        "last_loss": round(losses[-1], 4),
        "tokens_per_step": tokens_per_step,
        "step_s": round(step_s, 4) if step_s else None,
        "tokens_per_sec": (round(tokens_per_step / step_s, 1)
                           if step_s else None),
        # the launcher's own figure: wall over the whole loop, the
        # first step's compile included
        "tokens_per_sec_incl_compile": round(
            steps[-1]["tokens_per_sec"], 1),
    }
    say(f"train: {json.dumps(summary)}")
    return summary


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(method: str, url: str, body: Optional[dict] = None,
          timeout: float = 600.0) -> Tuple[int, bytes]:
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def parse_metrics(text: str) -> Dict[str, float]:
    """Prometheus text -> {series-with-labels: value} (exemplar
    suffixes dropped)."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        line = line.split(" # ", 1)[0]
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            continue
    return out


def smoke_requests(sizes: Sizes) -> List[Dict[str, Any]]:
    """The concurrent burst: every prompt length once greedy and once
    sampled. Prompts are seeded, so a rerun sends the same bytes."""
    import random

    rng = random.Random(0)
    reqs = []
    for i, n in enumerate(sizes.prompt_lens):
        prompt = [rng.randrange(sizes.vocab) for _ in range(n)]
        reqs.append({"prompt_tokens": [prompt],
                     "max_new_tokens": sizes.max_new})
        reqs.append({"prompt_tokens": [prompt],
                     "max_new_tokens": sizes.max_new, "temperature": 0.8,
                     "top_k": 40, "top_p": 0.95, "seed": 100 + i})
    return reqs


def phase_serve(sizes: Sizes, work_dir: str, *, device_count: int = 1
                ) -> Dict[str, Any]:
    os.makedirs(LOG_DIR, exist_ok=True)
    port = _free_port()
    extra = {"KFTPU_MODEL_BASE_PATH": os.path.join(work_dir, "models"),
             "KFTPU_REST_PORT": str(port),
             "KFTPU_GRPC_PORT": "0"}  # the smoke speaks REST only
    if device_count > 1:
        # every chip: tensor-parallel params + KV cache
        extra["KFTPU_SERVING_MESH"] = f"tp={device_count}"
    base = f"http://127.0.0.1:{port}"
    t0 = time.monotonic()
    with open(os.path.join(LOG_DIR, "serve.out"), "w") as out, \
            open(os.path.join(LOG_DIR, "serve.err"), "w") as err:
        server = subprocess.Popen(
            [sys.executable, "-m", "kubeflow_tpu.serving.server"],
            cwd=ROOT, env=child_env(extra), stdout=out, stderr=err)
        try:
            summary = _drive_server(sizes, server, base, t0)
        except BaseException:
            server.kill()
            server.wait()
            raise
        # SIGTERM is how a pod ends; the server must go, and the next
        # child must be able to open the chip it held
        server.send_signal(signal.SIGTERM)
        try:
            rc = server.wait(timeout=60)
        except subprocess.TimeoutExpired:
            server.kill()
            server.wait()
            raise PhaseError("serve", "server ignored SIGTERM for 60s"
                             ) from None
    # the server handles SIGTERM (engines closed, interpreter exit), it
    # does not die by it
    with open(os.path.join(LOG_DIR, "serve.err")) as f:
        require(rc == 0, "serve", f"server exit code {rc} after SIGTERM; "
                f"stderr tail:\n{_tail(f.read())}")
    summary["wall_s"] = round(time.monotonic() - t0, 1)
    say(f"serve: {json.dumps(summary)}")
    return summary


def _drive_server(sizes: Sizes, server: subprocess.Popen, base: str,
                  t_spawn: float) -> Dict[str, Any]:
    def stderr_tail() -> str:
        with open(os.path.join(LOG_DIR, "serve.err")) as f:
            return _tail(f.read())

    deadline = t_spawn + 600
    while True:
        require(server.poll() is None, "serve",
                f"server exited with {server.returncode} before it was "
                f"healthy; stderr tail:\n{stderr_tail()}")
        try:
            code, _ = _http("GET", base + "/healthz", timeout=5)
            if code == 200:
                break
        except (OSError, urllib.error.URLError):
            pass
        require(time.monotonic() < deadline, "serve",
                "server not healthy within 600s")
        time.sleep(1.0)
    healthy_after = time.monotonic() - t_spawn

    reqs = smoke_requests(sizes)
    url = f"{base}/v1/models/{MODEL_NAME}:generate"
    t0 = time.monotonic()
    with ThreadPoolExecutor(max_workers=len(reqs)) as pool:
        answers = list(pool.map(lambda b: _http("POST", url, b), reqs))
    wall = time.monotonic() - t0
    tokens = []
    for body, (code, raw) in zip(reqs, answers):
        require(code == 200, "serve",
                f"generate returned {code}: {raw[:300]!r}; server stderr "
                f"tail:\n{stderr_tail()}")
        rows = json.loads(raw)["tokens"]
        require(len(rows) == 1 and len(rows[0]) == sizes.max_new, "serve",
                f"wanted 1x{sizes.max_new} tokens, got {rows}")
        require(all(isinstance(t, int) and 0 <= t < sizes.vocab
                    for t in rows[0]), "serve",
                f"out-of-vocabulary token in {rows[0]}")
        tokens.append(rows[0])
    # determinism: the same greedy prompt, alone, twice in a row — the
    # same compiled programs over the same inputs must give the same
    # tokens. Its answer inside the burst is reported, not required to
    # match: there it rides other compiled programs (batch prefill,
    # the sampled step) whose bf16 logits differ from these by up to
    # 0.06, and on these random weights 3 % of decode steps flipped
    # their argmax between two programs, each at a top-2 gap of 0-2
    # bf16 ulps (measured on the v5e on the served weights, PERF.md
    # section 6, PR 21). That the programs agree to rounding and no
    # further is the kernels phase's prefill_batch_vs_row check.
    solo = []
    for _ in range(2):
        code, raw = _http("POST", url, reqs[0])
        require(code == 200, "serve", f"solo generate returned {code}")
        solo.append(json.loads(raw)["tokens"][0])
    require(solo[0] == solo[1], "serve",
            f"the same greedy prompt gave {solo[0]} then {solo[1]}")

    code, raw = _http("GET", base + "/metrics", timeout=30)
    require(code == 200, "serve", f"/metrics returned {code}")
    m = parse_metrics(raw.decode())
    label = f'{{model="{MODEL_NAME}"}}'
    n_sent = len(reqs) + len(solo)
    produced = n_sent * sizes.max_new
    engine_tokens = m.get("kftpu_engine_tokens_total" + label, 0.0)
    # the unary bucketed path never touches the engine's counters: the
    # engine having produced every returned token IS the proof that no
    # request fell to it
    require(engine_tokens >= produced, "serve",
            f"engine produced {engine_tokens:.0f} tokens but the "
            f"responses carried {produced}: some request bypassed the "
            "DecodeEngine")
    served = m.get("kftpu_serving_generate_requests_total" + label, 0.0)
    require(served == n_sent, "serve",
            f"{served:.0f} generate requests counted, sent {n_sent}")
    recoveries = m.get("kftpu_engine_recoveries_total" + label)
    require(recoveries == 0.0, "serve",
            f"engine recoveries = {recoveries} (want an exported 0)")
    warm_fail = m.get("kftpu_serving_warmup_failures_total" + label)
    require(warm_fail == 0.0, "serve",
            f"warm-up failures = {warm_fail} (want an exported 0)")
    return {
        "requests": n_sent,
        "burst_answer_equals_solo": tokens[0] == solo[0],
        "prefill_buckets": sorted({1 << (n - 1).bit_length()
                                   for n in sizes.prompt_lens}),
        "tokens_returned": produced,
        "engine_tokens_total": int(engine_tokens),
        "engine_steps_total": int(
            m.get("kftpu_engine_steps_total" + label, 0.0)),
        "recoveries": int(recoveries),
        "warmup_failures": int(warm_fail),
        "healthy_after_s": round(healthy_after, 1),
        # first requests pay the engine build + every prefill compile
        "burst_wall_s_incl_compile": round(wall, 1),
    }


def phase_kernels() -> Dict[str, Any]:
    # exit 1 = "ran every check, some failed": the verdicts are still
    # worth printing before the phase fails
    out = run_child("kernels", [os.path.abspath(__file__), "--phase",
                                "kernels"], timeout=1000, ok_codes=(0, 1))
    for line in out.splitlines():
        if line.startswith(("kernel ", "sharding ")):
            say(line)
    result = last_json_line("kernels", out)
    require(result["ok"], "kernels", f"failed checks: {result['failed']}")
    return result


def count_cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir)
                   if not n.endswith("-atime"))
    except OSError:
        return 0


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--phase", choices=("device", "kernels"), default=None,
                   help="internal: run one in-process child phase")
    args = p.parse_args(argv)
    if args.phase == "device":
        return child_device()
    if args.phase == "kernels":
        return child_kernels(FULL)

    from kubeflow_tpu.utils.compile_cache import compile_cache_dir

    t0 = time.monotonic()
    cache_dir = compile_cache_dir()
    entries0 = count_cache_entries(cache_dir)
    try:
        device = phase_device()
        say(f"device_kind={device['kind']!r} count={device['count']} "
            f"platform={device['platform']} jax={device['jax']} "
            f"libtpu={device['libtpu']} mesh={device['mesh']} "
            f"compile_cache={cache_dir} ({entries0} entries at start)")
        shutil.rmtree(WORK_DIR, ignore_errors=True)
        os.makedirs(WORK_DIR)
        try:
            train = phase_train(FULL, WORK_DIR, dp=device["mesh"]["dp"])
            serve = phase_serve(FULL, WORK_DIR,
                                device_count=device["count"])
        finally:
            shutil.rmtree(WORK_DIR, ignore_errors=True)
        kernels = phase_kernels()
    except PhaseError as e:
        print(f"chip_smoke: FAILED {e}", file=sys.stderr, flush=True)
        return 1
    entries1 = count_cache_entries(cache_dir)
    print(json.dumps({
        "wall_s": round(time.monotonic() - t0, 1),
        # warm = the cache held entries when the run began; a warm run
        # still writes the odd new entry (admission groupings, and so
        # prefill shapes, depend on request timing)
        "compile_cache": {"dir": cache_dir, "warm": entries0 > 0,
                          "entries_before": entries0,
                          "entries_after": entries1,
                          "kernels_child": kernels["compile_cache"]},
        "train": train, "serve": serve, "kernels": kernels}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# child side: the only code here that touches a jax backend
# ---------------------------------------------------------------------------


def child_device() -> int:
    import jax

    from kubeflow_tpu.ops.attention import resolve_interpret
    from kubeflow_tpu.parallel.mesh import auto_mesh_config

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no accelerator: jax.default_backend() is "
              f"{backend!r}, not 'tpu'", file=sys.stderr)
        return 3
    devs = jax.devices()
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — report only; absent off-chip
        libtpu = None
    print(json.dumps({
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "jax": jax.__version__, "libtpu": libtpu,
        "interpret": resolve_interpret(None),
        "mesh": dataclasses.asdict(auto_mesh_config(len(devs)))}))
    return 0


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def check_flash(sizes: Sizes, *, seq: int, batch: int, heads: int,
                causal: bool, masked: bool) -> Dict[str, Any]:
    """Flash fwd + fused dQ/dK/dV at the table tiles vs the O(S^2) oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.ops import autotune
    from kubeflow_tpu.ops.attention import (
        flash_attention,
        reference_attention,
    )

    D = sizes.head_dim
    ks = jax.random.split(jax.random.key(0), 4)
    q, k, v, g = (jax.random.normal(kk, (batch, seq, heads, D),
                                    jnp.bfloat16) for kk in ks)
    kv_len = None
    if masked:
        lens = np.linspace(seq // 4, seq, batch).astype(np.int32)
        kv_len = jnp.asarray(lens)
        # padded q rows are unspecified by contract: zero their cotangent
        g = g * (jnp.arange(seq)[None, :, None, None]
                 < kv_len[:, None, None, None]).astype(g.dtype)

    def fwd_bwd(attn):
        def f(q, k, v, g):
            out, vjp = jax.vjp(attn, q, k, v)
            return (out, *vjp(g))

        return jax.jit(f)(q, k, v, g)

    with autotune.record_resolutions() as rec:
        got = fwd_bwd(lambda q, k, v: flash_attention(
            q, k, v, causal, None, None, None, None, kv_len))
    want = fwd_bwd(lambda q, k, v: reference_attention(
        q, k, v, causal=causal, kv_len=kv_len))
    if masked:
        valid = (np.arange(seq)[None, :, None, None]
                 < np.asarray(kv_len)[:, None, None, None])
        got = (np.where(valid, np.asarray(got[0], np.float32), 0.0),
               *got[1:])
        want = (np.where(valid, np.asarray(want[0], np.float32), 0.0),
                *want[1:])
    errs = {n: _rel_err(a, b)
            for n, a, b in zip(("out", "dq", "dk", "dv"), got, want)}
    tiles = autotune.summarize_resolutions(rec)
    return {"rel_err": {n: round(e, 5) for n, e in errs.items()},
            "ok": all(math.isfinite(e) and e < KERNEL_REL_TOL
                      for e in errs.values()),
            "tiles": {t["kernel"]: [t["block_q"], t["block_k"],
                                    t["source"]] for t in tiles}}


def check_longcontext(sizes: Sizes) -> Dict[str, Any]:
    """``bench_longcontext`` for a few steps: the flash kernels inside
    the real remat'd train step, tiles resolved from the table."""
    from kubeflow_tpu.bench import suite

    row = suite.bench_longcontext(
        seq_len=sizes.flash_seq, steps=sizes.longcontext_steps, warmup=1,
        d_model=sizes.d_model, n_layers=sizes.n_layers,
        n_heads=sizes.n_heads, d_ff=sizes.d_ff)
    sources = sorted({t["source"] for t in row["tile_config"]})
    kernels = sorted({t["kernel"] for t in row["tile_config"]})
    # on more than one chip the kernels must be split over all of them,
    # no axis dropped (parallel/mesh.py:shard_kernel)
    placed = row["kernel_placement"]
    spread = all(not p["dropped"]
                 and math.prod(p["split"].values()) == p["devices"]
                 for p in placed)
    return {"ok": (sources == ["table"] and kernels == [
                       "flash_bwd_fused", "flash_fwd"]
                   and row["tokens_per_sec_per_chip"] > 0 and spread
                   and (row["n_chips"] == 1) == (not placed)),
            "tile_sources": sources, "kernels": kernels,
            "kernel_placement": placed,
            "tokens_per_sec_per_chip": row["tokens_per_sec_per_chip"],
            "step_time_ms": row["step_time_ms"], "mfu": row.get("mfu")}


def _engine_config(sizes: Sizes, *, n_layers: Optional[int] = None, **kw):
    from kubeflow_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=sizes.vocab, d_model=sizes.d_model,
        n_layers=n_layers or sizes.check_layers, n_heads=sizes.n_heads,
        n_kv_heads=sizes.n_heads, d_ff=sizes.d_ff,
        max_seq_len=sizes.seq_len, remat=False, **kw)


def check_paged(sizes: Sizes) -> Dict[str, Any]:
    """One decode step of the real model over a paged cache at engine
    shapes (``slots`` rows, page size 64, full width): the Pallas paged
    kernel vs the gather path, same params, same cache."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models import Transformer
    from kubeflow_tpu.models.decode import decode_step

    B, ps = sizes.slots, sizes.page_size
    n_log = sizes.seq_len // ps
    P = B * n_log
    cfg = _engine_config(sizes, kv_page_size=ps, kv_pages=P,
                         paged_attention_impl="kernel")
    tokens = jnp.zeros((B, 1), jnp.int32)
    variables = jax.jit(Transformer(cfg, decode=True).init)(
        jax.random.key(1), tokens)
    params, cache = variables["params"], variables["cache"]
    # ragged rows: one token, a page boundary on each side, mid-context,
    # the last position; the final row idle (all-sentinel, disarmed)
    S = sizes.seq_len
    positions = np.asarray(
        [0, ps - 1, ps, S // 3, S // 2, S - 2, 7, S][:B], np.int32)
    pages = np.full((B, n_log), P, np.int32)
    order = np.random.default_rng(0).permutation(P)
    for b in range(B):
        live = 0 if positions[b] >= S else positions[b] // ps + 1
        pages[b, :live] = order[b * n_log:b * n_log + live]
    kk = iter(jax.random.split(jax.random.key(2), 8))

    def fill(path, leaf):
        key = path[-1].key
        if key == "positions":
            return jnp.broadcast_to(jnp.asarray(positions),
                                    leaf.shape).astype(leaf.dtype)
        if key == "pages":
            return jnp.broadcast_to(jnp.asarray(pages),
                                    leaf.shape).astype(leaf.dtype)
        return jax.random.normal(next(kk), leaf.shape, leaf.dtype)

    cache = jax.tree_util.tree_map_with_path(fill, cache)
    token = jax.random.randint(jax.random.key(3), (B,), 0, sizes.vocab)
    logits = {}
    for impl in ("kernel", "gather"):
        c = dc.replace(cfg, paged_attention_impl=impl)
        logits[impl] = jax.jit(
            lambda p, ca, t, c=c: decode_step(c, p, ca, t)[0])(
                params, cache, token)
    live = positions < S
    err = _rel_err(np.asarray(logits["kernel"])[live],
                   np.asarray(logits["gather"])[live])
    return {"rel_err": round(err, 5),
            "ok": math.isfinite(err) and err < KERNEL_REL_TOL,
            "shape": {"rows": B, "page_size": ps, "pages": P,
                      "heads": sizes.n_heads, "head_dim": sizes.head_dim}}


def check_prefill_programs(sizes: Sizes) -> Dict[str, Any]:
    """The engine admits a lone request through the ``(1, S)`` row
    prefill and a burst of same-bucket requests through ONE ragged
    ``(B, S)`` batch prefill (``serving/engine.py:_admit_batch``): two
    compiled programs over the same prompt, and on the v5e a greedy
    prompt answered through both gave different tokens from the third
    one on (PR 21, chip call 7). A padding or masking fault in either
    program and bf16 rounding of a near-tied argmax look the same from
    outside the server; here the two are told apart, at the served
    width AND depth, on the smoke's own prompts:

    - the batch program's logits and KV cache for a prompt must equal
      the row program's within KERNEL_REL_TOL, and both must equal an
      unpadded ``(1, n)`` prefill of the same prompt (a pad that leaked
      into attention moves all of these by far more);
    - the same comparison in float32 at ``highest`` matmul precision
      must agree within F32_REL_TOL: rounding shrinks with the
      precision, a wrong mask does not;
    - greedy decode continues from both caches through ONE compiled
      step program, both fed the row path's tokens, so every step is
      comparable; each step's logits must agree within KERNEL_REL_TOL,
      and every step where the two argmaxes differ is recorded with
      the top-2 logit gap next to the cross-program difference.

    An argmax flip is therefore allowed only as what the tolerance
    already allows: a top-2 gap no wider than twice a logit difference
    that itself passed."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models import Transformer

    cfg = _engine_config(sizes, n_layers=sizes.n_layers)
    params = jax.jit(Transformer(cfg).init)(
        jax.random.key(7), jnp.zeros((1, 8), jnp.int32))["params"]
    greedy = [r["prompt_tokens"][0] for r in smoke_requests(sizes)
              if "temperature" not in r]
    # smallest, middle and largest bucket
    return compare_prefill_programs(cfg, params, greedy[::2], sizes.max_new)


def compare_prefill_programs(cfg, params, prompts: List[List[int]],
                             max_new: int) -> Dict[str, Any]:
    """:func:`check_prefill_programs` over one model and its prompts:
    the unpadded program for the first prompt, float32 for the first
    two, the bf16 programs and ``max_new`` decode steps for all. Apart
    from its seeded weights so that the same comparison can be pointed
    at an export (``load_latest(...).lm_config``/``.lm_params``), which
    is how the flip rate on the served weights was measured (PERF.md
    section 6, PR 21)."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.decode import decode_step, prefill
    from kubeflow_tpu.serving.engine import pow2_bucket

    leaves = cfg.cache_leaves(1)      # each cache leaf's row axis, by name

    cfg32 = dc.replace(cfg, dtype=jnp.float32)

    def programs(c):
        fn = jax.jit(lambda p, t, n: prefill(c, p, t, n))
        if c.dtype == jnp.float32:
            def at_highest(*a):
                with jax.default_matmul_precision("highest"):
                    return fn(*a)
            return at_highest
        return fn

    prefill16, prefill32 = programs(cfg), programs(cfg32)
    step = jax.jit(lambda p, ca, t: decode_step(cfg, p, ca, t))

    def burst(prompt: List[int]):
        """What ``_admit_batch`` builds for three same-bucket requests:
        the prompt twice (the pair that diverged), a shorter neighbour,
        and the length-1 pad row of the power-of-two batch."""
        n = len(prompt)
        rows = np.zeros((4, pow2_bucket(n, cfg.max_seq_len)), np.int32)
        lens = np.asarray([n, n, max(1, n - 2), 1], np.int32)
        rows[0, :n] = rows[1, :n] = prompt
        rows[2, :lens[2]] = prompt[::-1][:lens[2]]
        return jnp.asarray(rows), jnp.asarray(lens)

    def row0(cache):
        return jax.tree_util.tree_map_with_path(
            lambda path, x: jax.lax.slice_in_dim(
                x, 0, 1, axis=leaves[path[-1].key].batch_axis),
            cache)

    def live_kv(cache, n: int):
        """Row 0's written k/v at the prompt's real positions (the axis
        after the rows, whatever lies behind it)."""
        def live(x, rows):
            return jax.lax.slice_in_dim(jnp.take(x, 0, axis=rows), 0, n,
                                        axis=rows)

        return np.concatenate([
            np.asarray(live(x, leaves[path[-1].key].batch_axis),
                       np.float32).ravel()
            for path, x in jax.tree_util.tree_leaves_with_path(cache)
            if leaves[path[-1].key].heads_axis is not None])

    out: Dict[str, Any] = {"prompts": {}}
    worst16 = worst32 = 0.0
    for i, prompt in enumerate(prompts):
        n = len(prompt)
        rows, lens = burst(prompt)
        row_logits, row_cache = prefill16(params, rows[:1], lens[:1])
        bat_logits, bat_cache = prefill16(params, rows, lens)
        err = {  # bf16, the served dtype
            "batch_vs_row_logits": _rel_err(bat_logits[0], row_logits[0]),
            "batch_vs_row_kv": _rel_err(live_kv(bat_cache, n),
                                        live_kv(row_cache, n)),
            "batch_twin_rows_logits": _rel_err(bat_logits[1],
                                               bat_logits[0]),
        }
        err32 = {}
        if i == 0:
            # no pad anywhere: the (1, n) program
            exact, _ = prefill16(params, rows[:1, :n], None)
            err["row_vs_unpadded_logits"] = _rel_err(row_logits[0],
                                                     exact[0])
            err["batch_vs_unpadded_logits"] = _rel_err(bat_logits[0],
                                                       exact[0])
        if i < 2:
            r32, _ = prefill32(params, rows[:1], lens[:1])
            b32, _ = prefill32(params, rows, lens)
            err32["batch_vs_row_logits"] = _rel_err(b32[0], r32[0])
            if i == 0:
                e32, _ = prefill32(params, rows[:1, :n], None)
                err32["batch_vs_unpadded_logits"] = _rel_err(b32[0],
                                                             e32[0])
        # teacher-forced greedy continuation from both caches
        ca, cb = row_cache, row0(bat_cache)
        la = np.asarray(row_logits[0], np.float32)
        lb = np.asarray(bat_logits[0], np.float32)
        flips, gaps, diffs, scale = [], [], [], 0.0
        err["decode_logits"] = 0.0
        for t in range(max_new):
            top2 = np.sort(la)[-2:]
            gap, diff = float(top2[1] - top2[0]), float(
                np.max(np.abs(la - lb)))
            gaps.append(gap)
            diffs.append(diff)
            scale = max(scale, float(np.max(np.abs(la))))
            err["decode_logits"] = max(err["decode_logits"],
                                       _rel_err(lb, la))
            if int(la.argmax()) != int(lb.argmax()):
                flips.append({"token_index": t, "top2_gap": round(gap, 5),
                              "max_abs_logit_diff": round(diff, 5)})
            if t + 1 == max_new:
                break
            tok = jnp.asarray([int(la.argmax())], jnp.int32)
            (la, ca), (lb, cb) = step(params, ca, tok), step(params, cb,
                                                             tok)
            la = np.asarray(la[0], np.float32)
            lb = np.asarray(lb[0], np.float32)
        worst16 = max(worst16, *err.values())
        worst32 = max(worst32, 0.0, *err32.values())
        out["prompts"][str(n)] = {
            "bucket": int(rows.shape[1]),
            "rel_err": {k: float(f"{v:.3g}") for k, v in err.items()},
            "f32_rel_err": {k: float(f"{v:.3g}")
                            for k, v in err32.items()},
            "argmax_flips": flips,
            "min_top2_gap": round(min(gaps), 5),
            "median_top2_gap": round(float(np.median(gaps)), 5),
            "max_abs_logit_diff": round(max(diffs), 5),
            "max_abs_logit": round(scale, 3),
        }
    out["worst_rel_err"] = float(f"{worst16:.3g}")
    out["worst_f32_rel_err"] = float(f"{worst32:.3g}")
    out["flips"] = sum(len(r["argmax_flips"])
                       for r in out["prompts"].values())
    out["ok"] = bool(math.isfinite(worst16) and worst16 < KERNEL_REL_TOL
                     and math.isfinite(worst32)
                     and worst32 < F32_REL_TOL)
    return out


def check_sampler(sizes: Sizes) -> Dict[str, Any]:
    """The fused sampler vs ``sample_logits``' exact-sort path on one
    ``(slots, vocab)`` batch: greedy, top_k=1 and tiny-top_p rows must
    match exactly; filtered rows must land in the exact top-k set."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.decode import sample_logits
    from kubeflow_tpu.ops.sampling import fused_sample

    B, V = sizes.slots, sizes.vocab
    logits = 3.0 * jax.random.normal(jax.random.key(4), (B, V),
                                     jnp.float32)
    temps = np.asarray([0.0, 0.8, 1.0, 0.8, 0.0, 1.3, 0.7, 1.0][:B],
                       np.float32)
    top_k = np.asarray([0, 1, 1, 40, 40, 40, 0, 0][:B], np.int32)
    top_p = np.asarray([1.0, 1.0, 0.9, 0.95, 0.95, 1.0, 1e-6, 1.0][:B],
                       np.float32)
    keys = jax.random.split(jax.random.key(5), B)
    got = np.asarray(jax.jit(
        lambda l, k: fused_sample(l, k, temperature=temps, top_k=top_k,
                                  top_p=top_p))(logits, keys))
    want = np.asarray(jax.jit(
        lambda l: sample_logits(l, jax.random.key(6), temperature=temps,
                                top_k=top_k, top_p=top_p))(logits))
    host = np.asarray(logits)
    exact_rows = (temps <= 0) | (top_k == 1) | (top_p < 1e-3)
    exact_ok = bool((got[exact_rows] == want[exact_rows]).all()
                    and (got[exact_rows]
                         == host.argmax(-1)[exact_rows]).all())
    in_vocab = bool(((got >= 0) & (got < V)).all())
    support_ok = True
    for b in np.nonzero(top_k > 1)[0]:
        kth = np.sort(host[b])[-top_k[b]]
        support_ok &= bool(host[b, got[b]] >= kth)
    return {"ok": exact_ok and in_vocab and support_ok,
            "exact_rows_match": exact_ok, "in_vocab": in_vocab,
            "top_k_support": support_ok, "tokens": got.tolist()}


def check_sharding(sizes: Sizes) -> Dict[str, Any]:
    """More than one chip: the trainer's state and the engine's KV
    cache must be spread over the mesh, not parked on device 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models import Transformer
    from kubeflow_tpu.parallel import MeshConfig, create_mesh
    from kubeflow_tpu.parallel.mesh import (
        auto_mesh_config,
        record_kernel_placements,
    )
    from kubeflow_tpu.serving.engine import DecodeEngine
    from kubeflow_tpu.serving.model_store import shard_lm_params
    from kubeflow_tpu.train import (
        TrainState,
        create_sharded_state,
        make_optimizer,
    )

    n = jax.device_count()

    def spread(tree) -> Dict[str, Any]:
        """Of the leaves big enough to matter, how many are split and
        over how many devices the bytes actually sit."""
        big = [x for x in jax.tree_util.tree_leaves(tree)
               if getattr(x, "ndim", 0) >= 2]
        split = [x for x in big if x.addressable_shards[0].data.size
                 < x.size]
        devices = {s.device.id for x in big for s in x.addressable_shards}
        return {"leaves": len(big), "split_leaves": len(split),
                "devices": len(devices)}

    cfg = _engine_config(sizes)
    mesh_cfg = auto_mesh_config(n)
    mesh = create_mesh(mesh_cfg)
    model = Transformer(cfg)
    sample = jnp.zeros((mesh_cfg.dp, 128), jnp.int32)

    def init_fn(rng):
        return TrainState.create(
            apply_fn=model.apply,
            params=model.init(rng, sample)["params"],
            tx=make_optimizer(1e-3, warmup_steps=1, decay_steps=10))

    state, _ = create_sharded_state(init_fn, jax.random.key(0), mesh)
    out = {"mesh": dataclasses.asdict(mesh_cfg),
           "mesh_devices": np.vectorize(lambda d: d.id)(
               mesh.devices).tolist(),
           "params": spread(state.params),
           "opt_state": spread(state.opt_state)}
    # the engine tensor-parallel over every chip, on its Pallas paths:
    # paged decode kernel + fused sampler inside the serving mesh
    tp_mesh = create_mesh(MeshConfig(tp=n))
    host_params = jax.tree_util.tree_map(np.asarray, state.params)
    with record_kernel_placements() as placements:
        eng = DecodeEngine(cfg, shard_lm_params(host_params, tp_mesh),
                           slots=sizes.slots, steps_per_sync=4,
                           mesh=tp_mesh, paged=True,
                           kv_page_size=sizes.page_size,
                           paged_attention_impl="kernel",
                           sampler_impl="fused", sampler_bound=0,
                           autostart=False, name="sharding-check")
        out["kv_cache"] = spread(eng._cache)
        prompt = np.arange(1, 20, dtype=np.int32)
        reqs = [eng.submit(prompt, max_new=8),
                eng.submit(prompt, max_new=8, temperature=0.8, top_k=40,
                           top_p=0.95, seed=1)]
        while eng.active_count or eng.pending_count:
            eng.run_once(timeout=0.01)
    toks = [r.result() for r in reqs]
    out["tp_engine"] = {"tokens": [len(t) for t in toks],
                        "recoveries": eng.recoveries,
                        "paged_attention_impl": eng.paged_attention_impl,
                        "sampler_impl": eng.sampler_impl}
    eng.close()
    # how the engine's two kernels were placed on the tp mesh: the paged
    # kernel over every chip by heads; the sampler whole on every chip
    # by design (a row needs its whole vocab)
    out["tp_engine"]["kernel_placement"] = placements
    placed = {p["kernel"]: p for p in placements}
    out["ok"] = bool(
        all(len(t) == 8 and all(0 <= x < sizes.vocab for x in t)
            for t in toks)
        and eng.recoveries == 0 and mesh_cfg.tp > 1
        and len(placements) == 2
        and placed["paged_attn"] == {
            "kernel": "paged_attn", "devices": n, "split": {"heads": n},
            "dropped": []}
        and placed["fused_sampler"] == {
            "kernel": "fused_sampler", "devices": n, "split": {},
            "dropped": []}
        and all(out[k]["devices"] == n and out[k]["split_leaves"] > 0
                for k in ("params", "opt_state", "kv_cache")))
    return out


def child_kernels(sizes: Sizes) -> int:
    """Run every check, print one line each, fail if any failed. A
    kernel the compiler refuses is caught HERE — to be reported by name
    with the compiler's message next to the others' verdicts — and
    still fails the phase."""
    import traceback

    import jax

    from kubeflow_tpu.ops.attention import resolve_interpret
    from kubeflow_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # what the persistent cache did for THIS process (jax records one
    # event per executable looked up)
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    if jax.default_backend() != "tpu":
        print("chip_smoke: kernels need the TPU backend, have "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 3
    if resolve_interpret(None):
        print("chip_smoke: resolve_interpret(None) is True on the TPU "
              "backend", file=sys.stderr)
        return 3
    checks = [
        ("flash_causal", lambda: check_flash(
            sizes, seq=sizes.flash_seq, batch=1, heads=sizes.flash_heads,
            causal=True, masked=False)),
        ("flash_masked_bidirectional", lambda: check_flash(
            sizes, seq=sizes.masked_seq, batch=sizes.masked_batch,
            heads=sizes.masked_heads, causal=False, masked=True)),
        ("paged_decode", lambda: check_paged(sizes)),
        ("prefill_batch_vs_row", lambda: check_prefill_programs(sizes)),
        ("fused_sampler", lambda: check_sampler(sizes)),
        ("longcontext_train", lambda: check_longcontext(sizes)),
    ]
    if jax.device_count() > 1:
        checks.append(("sharding", lambda: check_sharding(sizes)))
    verdicts: Dict[str, Any] = {}
    for name, fn in checks:
        t0 = time.monotonic()
        try:
            res = fn()
        except Exception as e:  # noqa: BLE001 — reported, then fatal below
            traceback.print_exc()
            res = {"ok": False,
                   "error": f"{type(e).__name__}: {str(e)[:1500]}"}
        res["seconds"] = round(time.monotonic() - t0, 1)
        verdicts[name] = res
        tag = "sharding" if name == "sharding" else "kernel"
        print(f"{tag} {name}: {'ok' if res['ok'] else 'FAILED'} "
              f"{json.dumps(res)}", flush=True)
    failed = [n for n, r in verdicts.items() if not r["ok"]]
    print(json.dumps({"ok": not failed, "failed": failed,
                      "compile_cache": cache_events,
                      "checks": verdicts}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
