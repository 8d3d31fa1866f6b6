#!/usr/bin/env python3
"""chip_smoke.py — prove that ``train`` and ``serve`` still start on the chip.

    python3 chip_smoke.py                # the smoke; needs a TPU
    python3 chip_smoke.py --rehearse-cpu # toy-width rehearsal, labelled so

Drives the two main paths once through the entry point a user calls,
``akka_allreduce_tpu.cli.main`` (what ``python -m akka_allreduce_tpu.cli``
runs), at the full width of the flagship model — d_model 2048, 8 layers,
16 heads x 128, d_ff 8192, vocab 32768 (537M parameters), weights random
from a seed:

* ``train``: ``cli train --bf16 --batch 8 --seq 2048 --bucket-elems 4194304
  --dp 0`` for a few steps. Requires finite losses, ``min_count`` (the
  fewest data ranks any gradient bucket summed) equal to the device count,
  the Mosaic flash kernel in the step (the program's own trace-time
  ``attention[local]:`` notice — not interpret mode, not the reference),
  and exactly one compilation of the step program.
* ``serve-slot`` / ``serve-paged``: ``cli serve`` in float32 on the slot
  engine and with ``--paged``: every request completed, no failed attempt,
  decode tokens == requests x max_new_tokens, a bounded program count.
* ``serve-latent``: ``cli serve --model-config`` on one shortcut double
  layer at LongCat-Flash-Chat's published widths (latent attention 512 + 64
  x 64 heads, two held experts; bf16, 0.95B parameters), the same checks,
  and the fused latent decode kernel in the step: the program's
  ``attention[latent_decode]:`` notice has to say ``mosaic:``. A silent
  fall back to the pure-JAX formula on the chip is a failed smoke, not a
  slow benchmark cell.
* ``serve-sparse-latent``: ``cli serve --model-config --prefill-chunk
  2048`` on three layers at GLM-5.2's published widths (a dense and a
  sparse layer with an indexer each, a sparse layer that shares the
  second's choice; two held experts; bf16, 1.3B parameters): prompts of
  2,560 go through the cache in two chunks, then a few decode steps. The
  same checks, and: the programs' ``attention[sparse_latent]:`` notices
  (printed in the record) say which path each took over its 2,048 chosen
  rows - the chunk program the masked pass over the lane's key blocks, the
  decode step the gather - and the report's ``index`` counts show that the
  selection BOUND (the attentions read fewer rows than the indexers
  scored).

* ``serve-hybrid-ssm``: ``cli serve --model-config --prefill-chunk 2048``
  on three layers at Granite-4.0-H-Small's published widths (a state-space
  mixer, the attention without positions, a state-space mixer; two held
  experts of 72; bf16, 0.57B parameters): prompts of 2,560 are scanned
  through the lanes' recurrent state in two chunks (the second padded),
  then a few decode steps. The same checks, and: the programs'
  ``attention[ssm_scan]:`` notices say that the chunk program took the
  chunked scan at the published block of 256 and the step one step of the
  recurrence, and the report's ``ssm`` counts are what the dispatches say
  (the padding of the second chunk counted apart, advancing nothing).

One process per chip: this parent never imports JAX; each phase is a child
process (``--phase``) that owns the chip for its lifetime, checks that
``jax.devices()[0].platform`` is ``tpu`` before compiling anything, and
writes one JSON record (device, wall time split into compile and run,
persistent-cache hits and misses, peak device bytes). The records are
observations of a smoke, not benchmark numbers.

Exit code 0 and, as the last line of stdout,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
only if every phase met every requirement. Anything else — no TPU, an
``AATPU_PALLAS*`` kernel switch in the environment, a child that failed or
outlived the time limit, a requirement not met — exits non-zero and prints
no result line. The records also land in ``chiprun_out/chip_smoke.json``.
"""

from __future__ import annotations

import argparse
import io
import json
import math
import os
import re
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out")
TIME_LIMIT_S = 1150.0  # the contract allows 1200 s, compilation included
NO_TPU_EXIT = 3

FLAGSHIP = ["--d-model", "2048", "--n-layers", "8", "--n-heads", "16",
            "--d-ff", "8192", "--vocab", "32768"]
TOY = ["--d-model", "64", "--n-layers", "2", "--n-heads", "4",
       "--d-ff", "128", "--vocab", "256"]
TRAIN_STEPS = 5
# the ``serve-latent`` phase's model: one double layer of
# meituan-longcat/LongCat-Flash-Chat's config.json, every width as
# published, depth and the held experts cut (benchmark/configs/ has the
# cell's four layers and sixteen experts)
LATENT = dict(
    vocab_size=16384, hidden_size=6144, ffn_hidden_size=12288,
    expert_ffn_hidden_size=2048, num_layers=1, num_attention_heads=64,
    kv_lora_rank=512, q_lora_rank=1536, qk_rope_head_dim=64, v_head_dim=128,
    qk_nope_head_dim=128, mla_scale_q_lora=True, mla_scale_kv_lora=True,
    routed_scaling_factor=6, n_routed_experts=512, rms_norm_eps=1e-5,
    rope_theta=1e7, attention_method="MLA", zero_expert_num=256,
    zero_expert_type="identity", moe_topk=12, experts_held=[0, 2],
    torch_dtype="bfloat16")
LATENT_TOY = {**LATENT, "vocab_size": 256, "hidden_size": 64,
              "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32,
              "num_attention_heads": 4, "kv_lora_rank": 16,
              "q_lora_rank": 24, "qk_rope_head_dim": 8, "v_head_dim": 16,
              "qk_nope_head_dim": 16, "n_routed_experts": 16,
              "zero_expert_num": 8, "moe_topk": 4}


# the ``serve-sparse-latent`` phase's model: three layers of
# zai-org/GLM-5.2's config.json, every width as published, depth, the held
# experts and the vocabulary cut (benchmark/configs/ has the cell's five
# layers and sixteen experts)
SPARSE_LATENT = dict(
    model_type="glm_moe_dsa", vocab_size=19360, hidden_size=6144,
    intermediate_size=12288, moe_intermediate_size=2048,
    num_hidden_layers=3, num_attention_heads=64, kv_lora_rank=512,
    q_lora_rank=2048, qk_rope_head_dim=64, qk_nope_head_dim=192,
    v_head_dim=256, index_n_heads=32, index_head_dim=128, index_topk=2048,
    indexer_types=["full", "full", "shared"],
    mlp_layer_types=["dense", "sparse", "sparse"], n_routed_experts=256,
    n_shared_experts=1, num_experts_per_tok=8, norm_topk_prob=True,
    routed_scaling_factor=2.5, scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=1, topk_group=1, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 8000000, "rope_type": "default"},
    num_nextn_predict_layers=0, experts_held=[0, 2],
    torch_dtype="bfloat16")
SPARSE_LATENT_TOY = {
    **SPARSE_LATENT, "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "kv_lora_rank": 16, "q_lora_rank": 24,
    "qk_rope_head_dim": 8, "qk_nope_head_dim": 16, "v_head_dim": 16,
    "index_n_heads": 4, "index_head_dim": 16, "index_topk": 16,
    "n_routed_experts": 16, "num_experts_per_tok": 4}


# the ``serve-hybrid-ssm`` phase's model: three layers of
# ibm-granite/granite-4.0-h-small's config.json, every width as published,
# depth and the held experts cut (benchmark/configs/ has the cell's ten
# layers and 36 experts)
HYBRID_SSM = dict(
    model_type="granitemoehybrid", vocab_size=50176, hidden_size=4096,
    num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
    mamba_n_heads=128, mamba_d_head=64, mamba_expand=2, mamba_d_state=128,
    mamba_d_conv=4, mamba_chunk_size=256, mamba_n_groups=1,
    mamba_conv_bias=True, mamba_proj_bias=False, attention_bias=False,
    num_attention_heads=32, num_key_value_heads=8,
    attention_multiplier=0.0078125, position_embedding_type="nope",
    num_local_experts=72, num_experts_per_tok=10, intermediate_size=768,
    shared_intermediate_size=1536, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=16, rms_norm_eps=1e-5,
    tie_word_embeddings=True, hidden_act="silu", experts_held=[0, 2],
    torch_dtype="bfloat16")
HYBRID_SSM_TOY = {
    **HYBRID_SSM, "vocab_size": 256, "hidden_size": 64,
    "mamba_n_heads": 4, "mamba_d_head": 32, "mamba_d_state": 16,
    "mamba_chunk_size": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_local_experts": 8,
    "num_experts_per_tok": 3, "intermediate_size": 32,
    "shared_intermediate_size": 48}


def phase_argv(phase: str, rehearse: bool) -> "tuple[list[str], dict]":
    """The ``cli`` command line of a phase, and the numbers its checks
    compare against."""
    model = TOY if rehearse else FLAGSHIP
    if phase == "train":
        shape = (["--batch", "8", "--seq", "128", "--bucket-elems", "4096"]
                 if rehearse else
                 ["--batch", "8", "--seq", "2048",
                  "--bucket-elems", str(1 << 22)])
        return (["train", *model, "--bf16", *shape, "--dp", "0",
                 "--steps", str(TRAIN_STEPS), "--log-every", "1",
                 "--guard-recompiles"], {"steps": TRAIN_STEPS})
    want = ({"requests": 4, "max_new_tokens": 4} if rehearse
            else {"requests": 16, "max_new_tokens": 64})
    load = (["--slots", "2", "--max-seq", "64", "--prompt-len", "8:8"]
            if rehearse else
            ["--slots", "8", "--max-seq", "512", "--prompt-len", "128:128"])
    if phase == "serve-latent":
        # the file is written where the records go (git-ignored)
        path = os.path.join(OUT_DIR, "chip_smoke.serve-latent.config.json")
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(path, "w") as f:
            json.dump(LATENT_TOY if rehearse else LATENT, f)
        model = ["--model-config", path]
    if phase == "serve-sparse-latent":
        path = os.path.join(OUT_DIR,
                            "chip_smoke.serve-sparse-latent.config.json")
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(path, "w") as f:
            json.dump(SPARSE_LATENT_TOY if rehearse else SPARSE_LATENT, f)
        model = ["--model-config", path]
        # prompts a quarter past index_topk, through two chunks
        want = ({"requests": 2, "max_new_tokens": 4, "index_topk": 16}
                if rehearse else
                {"requests": 2, "max_new_tokens": 8, "index_topk": 2048})
        load = (["--slots", "2", "--max-seq", "64", "--prompt-len", "20:20",
                 "--prefill-chunk", "16"] if rehearse else
                ["--slots", "2", "--max-seq", "4096", "--prompt-len",
                 "2560:2560", "--prefill-chunk", "2048"])
    if phase == "serve-hybrid-ssm":
        path = os.path.join(OUT_DIR,
                            "chip_smoke.serve-hybrid-ssm.config.json")
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(path, "w") as f:
            json.dump(HYBRID_SSM_TOY if rehearse else HYBRID_SSM, f)
        model = ["--model-config", path]
        # prompts a quarter past a chunk: two chunks, the second padded
        want = ({"requests": 2, "max_new_tokens": 4, "prompt": 20,
                 "chunk": 16, "block": 8} if rehearse else
                {"requests": 2, "max_new_tokens": 8, "prompt": 2560,
                 "chunk": 2048, "block": 256})
        load = (["--slots", "2", "--max-seq", "64", "--prompt-len", "20:20",
                 "--prefill-chunk", "16"] if rehearse else
                ["--slots", "2", "--max-seq", "4096", "--prompt-len",
                 "2560:2560", "--prefill-chunk", "2048"])
    argv = ["serve", *model, *load, "--requests", str(want["requests"]),
            "--max-new-tokens", str(want["max_new_tokens"]),
            "--load", "closed"]
    if phase == "serve-paged":
        argv.append("--paged")
    return argv, want


PHASES = ("train", "serve-slot", "serve-paged", "serve-latent",
          "serve-sparse-latent", "serve-hybrid-ssm")
# one prompt length, so one prefill program; the rest are the decode
# step and first-use helpers. Exact-length prefill compiles one program
# per distinct length (ROADMAP S2) — a regression there shows here.
MAX_SERVE_PROGRAMS = 8


# -- the phase child (owns the chip) -------------------------------------

class _Tee(io.TextIOBase):
    """Collect what the CLI writes while passing it on to our stderr, so
    stdout carries nothing but the parent's result line."""

    def __init__(self):
        super().__init__()
        self.parts: "list[str]" = []

    def write(self, text: str) -> int:
        self.parts.append(text)
        return sys.__stderr__.write(text)

    def flush(self) -> None:
        sys.__stderr__.flush()

    def text(self) -> str:
        return "".join(self.parts)


def _check_train(out: str, err: str, want: dict, n_devices: int,
                 rehearse: bool, step_compiles: int) -> "list[dict]":
    steps = re.findall(
        r"^step\s+(\d+): loss (\S+) \(.*?tok/s\) \[min_count (\d+)\]",
        out, flags=re.M)
    losses = [float(x) for _, x, _ in steps]
    attn = re.findall(r"^attention\[local\]: (\S+) (.*)$", err, flags=re.M)
    kernel_ok = bool(attn) and all(
        impl.startswith("mosaic:flash") for impl, _ in attn)
    return [
        {"name": "every step logged a finite loss",
         "ok": len(steps) == want["steps"]
         and all(math.isfinite(x) for x in losses),
         "detail": losses},
        {"name": "min_bucket_count == n_devices",
         "ok": bool(steps) and all(int(c) == n_devices
                                   for _, _, c in steps),
         "detail": [int(c) for _, _, c in steps]},
        {"name": ("attention notice present (rehearsal: any implementation)"
                  if rehearse else
                  "Mosaic flash kernel in the train step"),
         "ok": bool(attn) if rehearse else kernel_ok,
         "detail": [" ".join(a) for a in attn]},
        {"name": "step program compiled exactly once",
         "ok": step_compiles == 1, "detail": step_compiles},
    ]


def _check_serve(out: str, want: dict) -> "list[dict]":
    lines = [ln for ln in out.strip().splitlines() if ln.startswith("{")]
    report = json.loads(lines[-1]) if lines else {}
    reqs = report.get("requests", {})
    decode = report.get("tokens", {}).get("decode")
    programs = report.get("compiled_programs")
    return [
        {"name": "completed == requests",
         "ok": reqs.get("completed") == want["requests"],
         "detail": reqs},
        {"name": "failed_attempts == 0",
         "ok": reqs.get("failed_attempts") == 0,
         "detail": reqs.get("failed_attempts")},
        {"name": "decode tokens == requests x max_new_tokens",
         "ok": decode == want["requests"] * want["max_new_tokens"],
         "detail": decode},
        {"name": f"compiled_programs <= {MAX_SERVE_PROGRAMS}",
         "ok": isinstance(programs, int)
         and 0 < programs <= MAX_SERVE_PROGRAMS,
         "detail": programs},
        {"name": "report names the devices the engine occupies",
         "ok": bool(report.get("devices")),
         "detail": report.get("devices")},
    ]


def _check_latent(err: str, rehearse: bool) -> "list[dict]":
    said = re.findall(r"^attention\[latent_decode\]: (\S+) (.*)$", err,
                      flags=re.M)
    kernel_ok = bool(said) and all(
        impl.startswith("mosaic:latent_decode_attention")
        for impl, _ in said)
    return [
        {"name": ("latent decode notice present (rehearsal: any "
                  "implementation)" if rehearse else
                  "Mosaic latent decode kernel in the step"),
         "ok": bool(said) if rehearse else kernel_ok,
         "detail": [" ".join(a) for a in said]},
    ]


def _check_sparse_latent(out: str, err: str, want: dict) -> "list[dict]":
    said = re.findall(r"^attention\[sparse_latent\]: (\S+) (.*)$", err,
                      flags=re.M)
    lines = [ln for ln in out.strip().splitlines() if ln.startswith("{")]
    index = (json.loads(lines[-1]) if lines else {}).get("index", {})
    layers, full = (len(SPARSE_LATENT["indexer_types"]),
                    SPARSE_LATENT["indexer_types"].count("full"))
    read = index.get("selected", 0) / layers
    scored = index.get("scanned", 0) / full
    # a chunk's queries share a lane (q=1xLx...): masked, in place; the
    # step holds a query a lane (q=<slots>x1x...): gathered
    took = {"chunk" if detail.startswith("q=1x") else "step": impl
            for impl, detail in said}
    return [
        {"name": f"attention over {want['index_topk']} chosen rows: in "
                 f"place in the chunk program, gathered in the step",
         "ok": took == {"chunk": "reference:_masked_latent_attention",
                        "step": "reference:_selected_latent_attention"}
         and all(f"chosen={want['index_topk']} " in detail
                 for _impl, detail in said),
         "detail": [" ".join(a) for a in said]},
        {"name": "the selection bound: rows read < keys scored, a layer",
         "ok": 0 < read < scored, "detail": index},
    ]


def _check_hybrid_ssm(out: str, err: str, want: dict) -> "list[dict]":
    said = re.findall(r"^attention\[ssm_scan\]: (\S+) (.*)$", err,
                      flags=re.M)
    lines = [ln for ln in out.strip().splitlines() if ln.startswith("{")]
    ssm = (json.loads(lines[-1]) if lines else {}).get("ssm", {})
    layers = HYBRID_SSM["layer_types"].count("mamba")
    chunks = -(-want["prompt"] // want["chunk"])
    # a chunk's tokens share a lane (q=1xLx...): the chunked scan; the step
    # holds a token a lane (q=<slots>x1x...): one step of the recurrence
    took = {"chunk" if detail.startswith("q=1x") else "step":
            (impl, detail) for impl, detail in said}
    return [
        {"name": f"the chunk program scans in blocks of {want['block']}, "
                 f"the step runs the recurrence once",
         "ok": set(took) == {"chunk", "step"}
         and took["chunk"][0] == "reference:_ssd_scan"
         and f"block={want['block']} " in took["chunk"][1] + " "
         and took["step"][0] == "reference:recurrence_step",
         "detail": [" ".join(a) for a in said]},
        {"name": "scan counts: the prompts' positions counted, the last "
                 "chunk's padding apart",
         "ok": ssm.get("scan_tokens") == layers * want["requests"]
         * want["prompt"]
         and ssm.get("scan_padded") == layers * want["requests"]
         * (chunks * want["chunk"] - want["prompt"])
         and ssm.get("lanes", 0) >= layers * want["requests"]
         * (want["max_new_tokens"] - 1),
         "detail": ssm},
    ]


def run_phase(phase: str, record_path: str, rehearse: bool) -> int:
    import contextlib

    import jax
    from jax import monitoring

    from akka_allreduce_tpu import cli
    from akka_allreduce_tpu.runtime.compile_cache import \
        enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not rehearse:
        print(f"chip_smoke: no TPU: jax.devices()[0].platform is "
              f"{dev.platform!r} ({device['count']} x {dev.device_kind}); "
              f"the smoke runs on the chip only (a CPU rehearsal is "
              f"`--rehearse-cpu`, and says so in its output)",
              file=sys.stderr)
        return NO_TPU_EXIT

    secs = {"trace": 0.0, "lower": 0.0, "compile": 0.0}
    programs: "dict[str, int]" = {}
    cache = {"hits": 0, "misses": 0}
    kinds = {"/jax/core/compile/jaxpr_trace_duration": "trace",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
             "/jax/core/compile/backend_compile_duration": "compile"}

    def on_duration(event: str, duration: float, **kw) -> None:
        kind = kinds.get(event)
        if kind is None:
            return
        secs[kind] += duration
        if kind == "compile":
            name = kw.get("fun_name", "?")
            programs[name] = programs.get(name, 0) + 1

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)

    argv, want = phase_argv(phase, rehearse)
    out, err = _Tee(), _Tee()
    print(f"chip_smoke[{phase}]: cli {' '.join(argv)}", file=sys.stderr)
    t0 = time.monotonic()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = cli.main(argv)
    wall = time.monotonic() - t0

    checks = [{"name": "cli exit code 0", "ok": rc == 0, "detail": rc}]
    if phase == "train":
        checks += _check_train(out.text(), err.text(), want,
                               device["count"], rehearse,
                               programs.get("jit(step)", 0))
    else:
        checks += _check_serve(out.text(), want)
        if phase == "serve-latent":
            checks += _check_latent(err.text(), rehearse)
        if phase == "serve-sparse-latent":
            checks += _check_sparse_latent(out.text(), err.text(), want)
        if phase == "serve-hybrid-ssm":
            checks += _check_hybrid_ssm(out.text(), err.text(), want)
    native = sys.modules.get("akka_allreduce_tpu.native")
    checks.append({"name": "native library not loaded on this path",
                   "ok": native is None or native._lib is None,
                   "detail": None})
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    compile_s = sum(secs.values())
    record = {
        "phase": phase, "rehearsal": rehearse, "argv": argv,
        "device": device, "ok": all(c["ok"] for c in checks),
        "checks": checks, "wall_s": round(wall, 2),
        "compile_s": round(compile_s, 2),
        "run_s": round(wall - compile_s, 2),
        "compile_split_s": {k: round(v, 2) for k, v in secs.items()},
        "programs_compiled": sum(programs.values()),
        "cache": {"dir": cache_dir, **cache},
        "peak_bytes_in_use": (max(p for p in peaks if p is not None)
                              if any(p is not None for p in peaks)
                              else None),
    }
    with open(record_path, "w") as f:
        json.dump(record, f, indent=1)
    return 0 if record["ok"] else 1


# -- the parent (never touches JAX) ---------------------------------------

def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy-width rehearsal on whatever backend JAX "
                         "finds; prints a line labelled rehearsal, never "
                         "the result line")
    ap.add_argument("--phase", choices=PHASES, help=argparse.SUPPRESS)
    ap.add_argument("--record", help=argparse.SUPPRESS)
    args = ap.parse_args()

    switches = sorted(k for k in os.environ if k.startswith("AATPU_PALLAS"))
    if switches:
        print(f"chip_smoke: refusing to run with {', '.join(switches)} "
              f"set: those variables swap a kernel for its reference, and "
              f"the smoke vouches for the kernels", file=sys.stderr)
        return 2
    if args.phase:
        return run_phase(args.phase, args.record, args.rehearse_cpu)

    os.makedirs(OUT_DIR, exist_ok=True)
    deadline = time.monotonic() + TIME_LIMIT_S
    records = []
    for phase in PHASES:
        record_path = os.path.join(OUT_DIR, f"chip_smoke.{phase}.json")
        if os.path.exists(record_path):
            os.unlink(record_path)
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
               "--record", record_path]
        if args.rehearse_cpu:
            cmd.append("--rehearse-cpu")
        # its own session, so a child stuck in the runtime (and anything
        # it started) can be killed as a group at the time limit
        started = time.monotonic()
        proc = subprocess.Popen(cmd, cwd=HERE, stdout=sys.stderr,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=max(1.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            _kill_group(proc)
            print(f"chip_smoke: FAILED: phase {phase} outlived the "
                  f"{TIME_LIMIT_S:.0f} s limit and was killed",
                  file=sys.stderr)
            return 1
        except BaseException:
            _kill_group(proc)
            raise
        record = None
        if os.path.exists(record_path):
            with open(record_path) as f:
                record = json.load(f)
            # the CLI call plus interpreter start, imports and reaching
            # the device
            record["process_wall_s"] = round(time.monotonic() - started, 2)
            records.append(record)
            for c in record["checks"]:
                print(f"chip_smoke[{phase}]: {'ok  ' if c['ok'] else 'FAIL'}"
                      f" {c['name']}: {c['detail']}", file=sys.stderr)
        if rc != 0 or record is None or not record["ok"]:
            print(f"chip_smoke: FAILED: phase {phase} exited {rc}"
                  + (" (no TPU)" if rc == NO_TPU_EXIT else ""),
                  file=sys.stderr)
            return rc or 1

    devices = [r["device"] for r in records]
    if any(d != devices[0] for d in devices):
        print(f"chip_smoke: FAILED: phases disagree about the device: "
              f"{devices}", file=sys.stderr)
        return 1
    label = "REHEARSAL (not a chip result) " if args.rehearse_cpu else ""
    for r in records:
        print(f"{label}{r['phase']}: process {r['process_wall_s']} s, "
              f"cli {r['wall_s']} s = compile "
              f"{r['compile_s']} s + run {r['run_s']} s; "
              f"{r['programs_compiled']} programs, cache "
              f"{r['cache']['hits']} hits / {r['cache']['misses']} misses "
              f"({r['cache']['dir']}); peak_bytes_in_use "
              f"{r['peak_bytes_in_use']}")
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        json.dump({"rehearsal": args.rehearse_cpu, "device": devices[0],
                   "phases": records}, f, indent=1)
    if args.rehearse_cpu:
        print(json.dumps({"rehearsal": True, "phases_ok": True,
                          "device": devices[0]}))
    else:
        print(json.dumps({"ok": True, "device": devices[0]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
