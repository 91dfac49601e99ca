#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the serve path still starts on the chip.

Drives the system's main path once, through the entry points a user calls:

  python -m comfyui_distributed_tpu.cli serve      (a child process)
    -> POST /prompt -> admission -> scheduler -> CLIP -> denoise -> VAE
    -> PNG -> GET /history

at the full width of SDXL-base, 1024x1024, with ``workflows/
distributed-sdxl.json`` exactly as shipped (three seeds), then the tiled
upscale ``workflows/distributed-upscale.json`` as shipped (512 -> 2048,
16 tiles, SD1.5 refine).  Weights are random, made from a seed
(``models/registry.py`` builds them when no checkpoint file exists), so
nothing is downloaded.  After the server has exited, a second child
compiles the Pallas flash-attention kernel (``interpret=False``) at the
shapes the UNets' attention rule sends it and holds its error against an
fp32 oracle to ``xla_attention``'s, then the fused GEGLU kernel at the
UNets' five feed-forward shapes against the float32 expression, then the
grouped few-row product at the routed experts' shapes against ``jnp.dot``
(and prints the rate at which it streams their weights).

This process never imports JAX: a chip belongs to one process at a time,
and a parent that touched JAX would hold it.  It talks to the server
over HTTP only, and the children run one after the other.

It never falls back.  No TPU, a failed request, a wrong image, a compile
in the steady state, a kernel the compiler refuses: each ends the script
non-zero with the reason on stderr and NOTHING on stdout.  On success
stdout holds two lines, each one JSON object.  The first is the summary
(also written to ``<out>/summary.json``): the device, the mesh axes, the
phases, and ``smoke_facts`` (per-request wall times, the compile-inclusive
first request, peak HBM, versions), ending with ``"claim": null``.  Its
timings are smoke facts (one run, compile included where it says so),
not benchmark numbers.  The last line is the result, and holds nothing
but the verdict and the device as JAX reports it:

  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}

``--rehearse`` is the CPU rehearsal tier-1 runs: the tiny family, small
sizes, ``JAX_PLATFORMS=cpu``, the kernel in interpret mode.  Both lines
then say ``"platform": "cpu"`` and the summary says ``"rehearsal": true``.

``--phases`` picks from ``sdxl,upscale,kernels`` (default: all three) so
a second run in one chip call can time a cache-warm first request
without paying for the rest again.  A fourth phase, ``lm``, is run only
when named: the language model of ``workflows/prompt-expand-txt2img.json``
at its published widths, served through ``POST /prompt`` by a server of
its own, and the logits of that request held to the plain float32
reference (``benchmarks/chip/verify_lm.py``, which says what is compared
and why each limit is what it is); then four such requests sent together,
which the server runs as the four rows of ONE execution, each row held
to the same reference inside the same limits; then the state-space
language model (granite-4.0-h-micro) the same two ways behind its
2048-position prompt, against a reference of its own
(``benchmarks/chip/verify_lm_ssm.py``; its requests share the operator's
1,950 instruction ids, so what is served there starts from their
snapshot), and a child of this script (`lm_prefix_child`) that holds
four rows started from that snapshot to the float32 reference of the
WHOLE prompt (logits, the state behind the prompt, the greedy ids) and
to the full path over the same prompts; then the language model of
Keye-VL-2.0-30B-A3B (a learned index picks 2,048 keys a query) behind its
8192-position prompt, against a reference forced to the program's expert
choices and key selections (``benchmarks/chip/verify_lm_dsa_moe.py``).
``lm_ssm`` runs the state-space model's two alone, ``lm_dsa`` that last
one.
"""

from __future__ import annotations

import argparse
import hashlib
import importlib.metadata
import io
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
import uuid

HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_PHASES = ("sdxl", "upscale", "kernels")
PHASES = DEFAULT_PHASES + ("lm", "lm_ssm", "lm_dsa")
SDXL_SEEDS = (777, 100777, 200777)   # far apart: fan-out replica r adds r

# (q [B, N, H, D], kv length M) at a CFG-stacked batch of 2: the
# self-attentions `models/layers.py:attention_path` sends to the kernel on
# a TPU, then two it keeps on the XLA path (``attn_impl="pallas"`` can
# still force the kernel there, so it has to compile); M=77 is the text
# cross-attention
KERNEL_SHAPES = (
    ((2, 4096, 10, 64), 4096),   # SDXL level 1 self-attention
    ((2, 1024, 20, 64), 1024),   # SDXL level 2 / mid self-attention
    ((2, 4096, 8, 40), 4096),    # SD1.5 level 0 self-attention
    ((2, 1024, 8, 80), 1024),    # SD1.5 level 1 self-attention
    ((2, 9216, 5, 64), 9216),    # SD2.1 at 768x768, level 0
    ((2, 256, 8, 160), 256),     # SD1.5 level 2 self-attention
    ((2, 4096, 10, 64), 77),     # SDXL cross-attention
)
KERNEL_SHAPES_REHEARSAL = (((1, 200, 2, 16), 200), ((2, 64, 2, 16), 77))
# The kernel is held to the precision of the path it replaces: its error
# against an fp32 oracle (max |diff| over max |ref|) may be at most this
# many times `xla_attention`'s against the same oracle.  The floor is for
# the fp32 rehearsal, where both errors are a few ulps.
KERNEL_ERR_RATIO = 1.25
KERNEL_ERR_FLOOR = 4e-6

# x [B, T, c] against ``proj [c, 8c]`` at a CFG-stacked batch of 2: the
# GEGLU call sites of the two benchmarked UNets, each a shape
# `models/layers.py:geglu_path` sends to the fused kernel on a TPU
GEGLU_SHAPES = (
    (2, 4096, 640), (2, 1024, 1280),                    # SDXL
    (2, 4096, 320), (2, 1024, 640), (2, 256, 1280),     # SD1.5
)
GEGLU_SHAPES_REHEARSAL = ((2, 64, 128),)
# The fused GEGLU against ``a * gelu(b, approximate=False)`` of the same
# bf16 operands in float32 at the highest matmul precision: max |diff|
# over max |ref| at most one bf16 ulp of the largest element (2^-8).  The
# kernel rounds ONCE, the gated fp32 accumulators to bf16: half an ulp,
# and the rest is room for the order of the MXU's fp32 sums.  The module
# as written rounds the projection first and the gated product again, so
# it is held beside the kernel and may do no better: the limit is not one
# the path it replaces would have met with more to spare.
GEGLU_ERR_LIMIT = 2.0 ** -8

# The grouped few-row product (``ops/pallas/fewrow_dense.py``
# `fewrow_grouped`) at the routed experts' published shapes: (name,
# experts in a block, K, N, leaves of one call, slots, live slots), the
# live slots a decode step's mean (Keye: 21 of 128 hit a block; the two
# 16-expert shares 1-2) and every slot
GROUPED_SHAPES = (
    ("keye gate_proj+up_proj", 128, 2048, 768, 2, 32, 21),
    ("keye down_proj", 128, 768, 2048, 1, 32, 21),
    ("keye gate_proj+up_proj, every slot", 128, 2048, 768, 2, 32, 32),
    ("pangu gate_proj+up_proj", 16, 7680, 2048, 2, 16, 2),
    ("pangu down_proj", 16, 2048, 7680, 1, 16, 2),
    ("pangu down_proj, every slot", 16, 2048, 7680, 1, 16, 16),
)
GROUPED_SHAPES_REHEARSAL = (("tiny gate_proj+up_proj", 4, 256, 128, 2, 4, 3),
                            ("tiny down_proj", 4, 128, 256, 1, 4, 0))
# Against ``jnp.dot`` of the same bf16 operands in float32 at the highest
# precision: max |diff| over max |ref|.  Nothing is rounded to bf16 on the
# way (bf16 x bf16 is exact in float32, the sums and the result are
# float32), so only the ORDER of the float32 sums over K differs: a few
# float32 ulps times sqrt(K).  2^-14 is a thousand float32 ulps and a
# sixty-fourth of one bf16 ulp: a result that passed through bf16, or a
# slot that read another expert, cannot meet it.
GROUPED_ERR_LIMIT = 2.0 ** -14


class SmokeFailure(Exception):
    """One phase missed; the script exits non-zero and prints no result."""


def check(cond: bool, why: str) -> None:
    if not cond:
        raise SmokeFailure(why)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# --- HTTP (stdlib only) ------------------------------------------------------

def get_json(url: str, timeout: float = 60.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def post_json(url: str, payload, timeout: float = 60.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def upload_png(base: str, name: str, png: bytes) -> None:
    boundary = uuid.uuid4().hex
    body = (f"--{boundary}\r\nContent-Disposition: form-data; "
            f'name="image"; filename="{name}"\r\n'
            f"Content-Type: image/png\r\n\r\n").encode() \
        + png + f"\r\n--{boundary}--\r\n".encode()
    req = urllib.request.Request(
        f"{base}/upload/image", data=body,
        headers={"Content-Type":
                 f"multipart/form-data; boundary={boundary}"})
    with urllib.request.urlopen(req, timeout=60.0) as r:
        check(json.loads(r.read()).get("name") == name,
              f"/upload/image did not store {name}")


# --- the server child --------------------------------------------------------

class Server:
    """``cli serve`` as a child process with its working directory under
    the output directory, so ``output/``, ``input/``, ``logs/`` and
    ``cluster_config.json`` land there and not in the checkout."""

    def __init__(self, out_dir: str, env: dict):
        self.cwd = os.path.join(out_dir, "server")
        os.makedirs(self.cwd, exist_ok=True)
        self.log_path = os.path.join(out_dir, "server.stderr.log")
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        self.base = f"http://127.0.0.1:{port}"
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "comfyui_distributed_tpu.cli", "serve",
             "--host", "127.0.0.1", "--port", str(port),
             "--config", os.path.join(self.cwd, "cluster_config.json")],
            cwd=self.cwd, env=env, stdout=self._log,
            stderr=subprocess.STDOUT)

    def log_tail(self, n: int = 3000) -> str:
        with open(self.log_path, "rb") as f:
            return f.read()[-n:].decode("utf-8", "replace")

    def require_alive(self) -> None:
        rc = self.proc.poll()
        check(rc is None,
              f"server child exited with code {rc}:\n{self.log_tail()}")

    def wait_ready(self, timeout: float = 300.0) -> dict:
        """First answer of /distributed/status (it builds the mesh)."""
        deadline = time.monotonic() + timeout
        while True:
            self.require_alive()
            try:
                return get_json(f"{self.base}/distributed/status")
            except (urllib.error.URLError, ConnectionError, OSError):
                check(time.monotonic() < deadline,
                      f"server not answering after {timeout:.0f}s:\n"
                      f"{self.log_tail()}")
                time.sleep(0.5)

    def run_prompt(self, prompt: dict, timeout: float):
        """POST /prompt, await the id on /history.  Returns (history
        entry, wall seconds from POST to the entry appearing)."""
        t0 = time.monotonic()
        pid = post_json(f"{self.base}/prompt",
                        {"prompt": prompt,
                         "client_id": "chip_smoke"})["prompt_id"]
        while True:
            self.require_alive()
            hist = get_json(f"{self.base}/history")
            if pid in hist:
                return hist[pid], time.monotonic() - t0
            check(time.monotonic() - t0 < timeout,
                  f"prompt {pid} not in /history after {timeout:.0f}s:\n"
                  f"{self.log_tail()}")
            time.sleep(0.25)

    def metrics(self) -> dict:
        return get_json(f"{self.base}/distributed/metrics")

    def shut_down(self, timeout: float = 120.0) -> int:
        """SIGTERM -> aiohttp's graceful exit (drain, close).  Returns the
        exit code."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=timeout)
        finally:
            self.kill()

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()


def load_workflow(name: str) -> dict:
    with open(os.path.join(HERE, "workflows", name), encoding="utf-8") as f:
        doc = json.load(f)
    # "__doc__" is the fixture's comment, not a node
    return {k: v for k, v in doc.items() if isinstance(v, dict)}


def read_png(path: str):
    import numpy as np
    from PIL import Image
    with Image.open(path) as im:
        return np.asarray(im.convert("RGB"))


def node_seconds(metrics: dict) -> dict:
    """Host wall seconds spent in each workflow node type so far (the
    server's own per-node histogram; dispatch is asynchronous, so a
    device wait shows in the node that first needs the value)."""
    return {k: float(v["total_s"]) for k, v in metrics["nodes"].items()}


def check_all_differ(images, what: str) -> None:
    import numpy as np
    for a in range(len(images)):
        for b in range(a + 1, len(images)):
            check(not np.array_equal(images[a], images[b]),
                  f"{what} {a} and {b} gave the same image")


def sdxl_phase(server: Server, cfg: dict, data_axis: int, facts: dict):
    """Three SDXL prompts, three seeds; every image checked."""
    out_dir = os.path.join(server.cwd, "output")
    firsts = []
    walls = []
    metrics = server.metrics()
    for i, seed in enumerate(SDXL_SEEDS):
        wf = load_workflow("distributed-sdxl.json")
        wf["5"]["inputs"]["seed"] = seed
        if cfg["rehearsal"]:
            wf["2"]["inputs"].update(width=cfg["sdxl_px"],
                                     height=cfg["sdxl_px"])
            wf["6"]["inputs"]["steps"] = cfg["steps"]
        before = set(os.listdir(out_dir)) if os.path.isdir(out_dir) \
            else set()
        compiles_before = metrics["retraces"]["compiles"]
        entry, wall = server.run_prompt(wf, cfg["first_timeout"] if i == 0
                                        else cfg["steady_timeout"])
        metrics = server.metrics()
        compiled = metrics["retraces"]["compiles"] - compiles_before
        walls.append(round(wall, 2))
        say(f"sdxl seed {seed}: {entry.get('status')} in {wall:.1f}s, "
            f"{compiled} compile(s)")
        check(entry.get("status") == "success",
              f"sdxl seed {seed} ended {entry}:\n{server.log_tail()}")
        check(entry.get("images") == data_axis,
              f"sdxl seed {seed}: {entry.get('images')} image(s), the "
              f"mesh's data axis is {data_axis}")
        if i == 0:
            facts["first_request_compiles"] = compiled
            first_nodes = node_seconds(metrics)
            facts["first_request_node_s"] = {
                k: round(v, 2) for k, v in first_nodes.items() if v >= 0.05}
        else:
            check(compiled == 0,
                  f"sdxl request {i + 1} compiled {compiled} program(s); "
                  f"the steady state must compile nothing")
        new = sorted(set(os.listdir(out_dir)) - before)
        check(len(new) == data_axis,
              f"sdxl seed {seed}: {len(new)} new PNG(s) {new}, expected "
              f"{data_axis}")
        imgs = [read_png(os.path.join(out_dir, n)) for n in new]
        side = cfg["sdxl_out_px"]
        for name, im in zip(new, imgs):
            check(im.shape == (side, side, 3),
                  f"{name} decodes to {im.shape}, expected {side}x{side}")
            check(float(im.std()) > 1.0,
                  f"{name} is a constant image (std {im.std():.3f}): a "
                  f"NaN or saturated latent decodes to one")
        check_all_differ(imgs, f"seed {seed}: replicas")
        firsts.append(imgs[0])
    check_all_differ(firsts, f"seeds {SDXL_SEEDS}: requests")
    facts["sdxl_request_wall_s"] = walls
    steady = {k: (v - first_nodes.get(k, 0.0)) / (len(SDXL_SEEDS) - 1)
              for k, v in node_seconds(metrics).items()}
    facts["steady_request_node_s"] = {
        k: round(v, 3) for k, v in steady.items() if v >= 0.005}
    facts["first_request_s_compile_inclusive"] = walls[0]
    # same seed, same program => same pixels, run to run and cache or not
    facts["first_image_sha256"] = hashlib.sha256(
        firsts[0].tobytes()).hexdigest()[:16]


def memory_facts(server: Server, cfg: dict, n_devices: int,
                 weights_resident: bool, facts: dict):
    """The resource probe once the requests are done: real allocator
    numbers, and on several chips a shard of the work on every one."""
    time.sleep(cfg["monitor_interval_s"] + 1.0)   # a sample taken after
    res = get_json(f"{server.base}/distributed/resource")["resources"]
    facts["memory_source"] = res["source"]
    if cfg["rehearsal"]:
        return
    check(res["source"] == "memory_stats",
          f"resource probe reports source {res['source']!r}, not the "
          f"device allocator's memory_stats")
    per_device = res["per_device_bytes"]
    check(len(per_device) == n_devices,
          f"memory_stats from {len(per_device)} of {n_devices} devices")
    facts["per_device_bytes_in_use"] = [d[0] for d in per_device]
    facts["peak_hbm_bytes_per_device"] = [d[1] for d in per_device]
    facts["peak_hbm_bytes"] = max(d[1] for d in per_device)
    if not weights_resident:
        return
    for i, (in_use, _) in enumerate(per_device):
        # SDXL's bf16 towers are ~6.9 GB whole and ~3.5 GB split in two
        check(in_use > 2 ** 30,
              f"device {i} holds {in_use} bytes after the SDXL requests: "
              f"everything sits on another chip")


def upscale_phase(server: Server, cfg: dict, facts: dict):
    """Upload a seeded PNG, run the tiled upscale as shipped."""
    import numpy as np
    from PIL import Image
    side = cfg["upscale_in_px"]
    src = (np.random.default_rng(0).random((side, side, 3)) * 255
           ).astype("uint8")
    buf = io.BytesIO()
    Image.fromarray(src).save(buf, "PNG")
    upload_png(server.base, "input.png", buf.getvalue())
    wf = load_workflow("distributed-upscale.json")
    if cfg["rehearsal"]:
        wf["16"]["inputs"].update(width=cfg["upscale_out_px"],
                                  height=cfg["upscale_out_px"])
        wf["2"]["inputs"].update(steps=1, tile_width=64, tile_height=64,
                                 padding=8, mask_blur=2)
    entry, wall = server.run_prompt(wf, cfg["first_timeout"])
    say(f"upscale: {entry.get('status')} in {wall:.1f}s")
    check(entry.get("status") == "success",
          f"upscale ended {entry}:\n{server.log_tail()}")
    out = cfg["upscale_out_px"]
    check(entry.get("image_shapes") == [[out, out, 3]],
          f"upscale output {entry.get('image_shapes')}, expected one "
          f"{out}x{out} image")
    facts["upscale_wall_s_compile_inclusive"] = round(wall, 2)


def server_phases(phases, cfg: dict, out_dir: str, env: dict,
                  result: dict) -> None:
    facts = result["smoke_facts"]
    server = Server(out_dir, env)
    try:
        t0 = time.monotonic()
        status = server.wait_ready()
        facts["server_ready_s"] = round(time.monotonic() - t0, 2)
        dev0 = status["devices"][0]
        result["device"] = {"platform": status["platform"],
                            "kind": dev0["kind"],
                            "count": status["num_devices"]}
        result["mesh_axes"] = status["axes"]
        say(f"server up: {result['device']} mesh {status['axes']}")
        check(status["platform"] == cfg["platform"],
              f"/distributed/status reports platform "
              f"{status['platform']!r}, not {cfg['platform']!r}")
        if "sdxl" in phases:
            sdxl_phase(server, cfg, int(status["axes"]["data"]), facts)
        if "upscale" in phases:
            upscale_phase(server, cfg, facts)
        memory_facts(server, cfg, int(status["num_devices"]),
                     "sdxl" in phases, facts)
        counters = server.metrics()["pipeline"]["counters"]
        facts["compile_cache_hits"] = counters.get("compile_cache_hits", 0)
        facts["compile_cache_writes"] = counters.get(
            "compile_cache_writes", 0)
        server.require_alive()
        rc = server.shut_down()
        check(rc == 0, f"server child exited with code {rc} on SIGTERM:\n"
                       f"{server.log_tail()}")
        check("Traceback" not in server.log_tail(1 << 30),
              f"traceback in the server's stderr ({server.log_path}):\n"
              f"{server.log_tail()}")
    finally:
        server.kill()


# --- the kernel child --------------------------------------------------------

def kernel_child(rehearse: bool) -> int:
    """Runs in its own process (it owns the chip while it lives): compile
    the Pallas kernel at each shape and compare it and ``xla_attention``
    with an fp32 oracle, then the GEGLU kernel (`geglu_shapes`) and the
    grouped few-row product (`grouped_shapes`).  Prints one JSON line;
    any refusal, or an error over KERNEL_ERR_RATIO x ``xla_attention``'s,
    over GEGLU_ERR_LIMIT or over GROUPED_ERR_LIMIT, raises."""
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.models.layers import (attention_path,
                                                       xla_attention)
    from comfyui_distributed_tpu.ops.pallas.flash_attention import \
        flash_attention
    from comfyui_distributed_tpu.runtime.manager import \
        enable_persistent_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != ("cpu" if rehearse else "tpu"):
        raise SystemExit(f"kernel phase: platform is {platform!r}")
    enable_persistent_compile_cache()

    def oracle(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        s = jnp.einsum("bnhd,bmhd->bhnm", q, k, precision="highest") \
            / math.sqrt(q.shape[-1])
        return jnp.einsum("bhnm,bmhd->bnhd", jax.nn.softmax(s, axis=-1),
                          v, precision="highest")

    rows, failures = [], []
    shapes = KERNEL_SHAPES_REHEARSAL if rehearse else KERNEL_SHAPES
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    for (b, n, h, d), m in shapes:
        rng = np.random.default_rng(n * 31 + m)
        q = jnp.asarray(rng.standard_normal((b, n, h, d)), dtype)
        k = jnp.asarray(rng.standard_normal((b, m, h, d)), dtype)
        v = jnp.asarray(rng.standard_normal((b, m, h, d)), dtype)
        ref = np.asarray(jax.jit(oracle)(q, k, v))

        def rel_err(out):
            return float(np.max(np.abs(np.asarray(out, np.float32) - ref))
                         / np.max(np.abs(ref)))

        err_xla = rel_err(jax.jit(lambda q, k, v: xla_attention(
            q, k, v, 1.0 / math.sqrt(d)))(q, k, v))
        try:
            out = jax.jit(lambda q, k, v: flash_attention(
                q, k, v, interpret=rehearse))(q, k, v)
        except Exception as e:  # noqa: BLE001 - report every shape, then fail
            failures.append(f"q {(b, n, h, d)} M={m}: the compiler refused "
                            f"it: {type(e).__name__}: {str(e)[:1500]}")
            continue
        err = rel_err(out)
        if not (err <= max(KERNEL_ERR_RATIO * err_xla, KERNEL_ERR_FLOOR)):
            failures.append(
                f"q {(b, n, h, d)} M={m}: rel err {err:.5f} against the "
                f"fp32 oracle, xla_attention's is {err_xla:.5f} (at most "
                f"{KERNEL_ERR_RATIO} x allowed)")
        rows.append({"q": [b, n, h, d], "kv_len": m,
                     "path": attention_path(platform, b, n, m, h),
                     "rel_err": round(err, 6),
                     "rel_err_xla": round(err_xla, 6)})
    geglu_rows = geglu_shapes(rehearse, platform, failures)
    grouped_rows = grouped_shapes(rehearse, failures)
    if failures:
        raise SystemExit("kernel phase failed:\n" + "\n".join(failures))
    print(json.dumps({"device": {"platform": platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)},
                      "interpret": rehearse, "shapes": rows,
                      "geglu_shapes": geglu_rows,
                      "grouped_shapes": grouped_rows}), flush=True)
    return 0


def geglu_shapes(rehearse: bool, platform: str, failures: list) -> list:
    """The fused GEGLU kernel (``ops/pallas/geglu.py``) and the module as
    written, each against the float32 expression on the same operands, at
    the published shapes: the kernel inside GEGLU_ERR_LIMIT, and today's
    path no nearer the oracle than the kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.models.layers import geglu_path
    from comfyui_distributed_tpu.ops.pallas.geglu import geglu, xla_geglu

    def oracle(x, kernel, bias):
        with jax.default_matmul_precision("highest"):
            return xla_geglu(*(a.astype(jnp.float32)
                               for a in (x, kernel, bias)))

    rows = []
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    for b, t, c in GEGLU_SHAPES_REHEARSAL if rehearse else GEGLU_SHAPES:
        rng = np.random.default_rng(t * 31 + c)
        x = jnp.asarray(rng.standard_normal((b, t, c)), dtype)
        kernel = jnp.asarray(rng.standard_normal((c, 8 * c)) / np.sqrt(c),
                             dtype)
        bias = jnp.asarray(rng.standard_normal((8 * c,)) * 0.1, dtype)
        ref = np.asarray(jax.jit(oracle)(x, kernel, bias))

        def rel_err(out):
            return float(np.max(np.abs(np.asarray(out, np.float32) - ref))
                         / np.max(np.abs(ref)))

        err_xla = rel_err(jax.jit(xla_geglu)(x, kernel, bias))
        try:
            err = rel_err(jax.jit(lambda x, k, b: geglu(
                x, k, b, rehearse))(x, kernel, bias))
        except Exception as e:  # noqa: BLE001 - report every shape, then fail
            failures.append(f"geglu x {(b, t, c)}: the compiler refused "
                            f"it: {type(e).__name__}: {str(e)[:1500]}")
            continue
        if not (err <= GEGLU_ERR_LIMIT
                and err <= max(err_xla, KERNEL_ERR_FLOOR)):
            failures.append(
                f"geglu x {(b, t, c)}: rel err {err:.6f} against the fp32 "
                f"oracle (limit {GEGLU_ERR_LIMIT:.6f}); the module as "
                f"written reads {err_xla:.6f}")
        rows.append({"x": [b, t, c], "path": geglu_path(platform, b * t, c),
                     "rel_err": round(err, 6),
                     "rel_err_xla": round(err_xla, 6)})
    return rows


def grouped_shapes(rehearse: bool, failures: list) -> list:
    """The grouped few-row product against ``jnp.dot`` on
    ``leaf[l, ids[s]]`` for every live slot, inside GROUPED_ERR_LIMIT, at
    the routed experts' shapes and 4 rows; and the rate at which it
    streams the live slots' weights (16 calls in one program, each over
    other experts: the bytes of the experts read over the seconds of a
    call)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.ops.pallas.fewrow_dense import \
        fewrow_grouped

    rows, layers, calls = [], 2, 16
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    for name, held, k, n, count, slots, live in \
            GROUPED_SHAPES_REHEARSAL if rehearse else GROUPED_SHAPES:
        rng = np.random.default_rng(k * 31 + n + live)
        keys = jax.random.split(jax.random.PRNGKey(k + n), count)
        leaves = [(jax.random.normal(key, (layers, held, k, n), jnp.float32)
                   / np.sqrt(k)).astype(dtype) for key in keys]
        shared = count == 2            # gate / up meet the call's rows
        x = jnp.asarray(rng.standard_normal(
            (4, k) if shared else (slots, 4, k)), dtype)
        # ascending distinct experts a call, as `_routed` hands them over
        ids = jnp.asarray(np.stack([
            np.sort(rng.permutation(held)[:slots]) for _ in range(calls)]),
            jnp.int32)

        def call(x, leaves, l, ids):
            return fewrow_grouped(x, leaves, l, ids, live,
                                  interpret=rehearse)

        try:
            out = jax.jit(call)(x, leaves, 1, ids[0])
            worst = 0.0
            for o, w in zip(out, leaves):
                for s in range(live):
                    ref = np.asarray(jnp.dot(
                        (x if shared else x[s]).astype(jnp.float32),
                        w[1, ids[0, s]].astype(jnp.float32),
                        precision="highest"))
                    worst = max(worst, float(
                        np.max(np.abs(np.asarray(o[s]) - ref))
                        / np.max(np.abs(ref))))

            @jax.jit
            def many(x, leaves, ids):
                def one(total, own):
                    out = call(x, leaves, own[0] % layers, own)
                    return total + sum(
                        jnp.sum(o[:live]) for o in out), None
                return jax.lax.scan(one, jnp.float32(0), ids)[0]

            seconds = None         # a rate is the chip's to give
            if not rehearse:
                many(x, leaves, ids).block_until_ready()
                t0 = time.perf_counter()
                many(x, leaves, ids).block_until_ready()
                seconds = (time.perf_counter() - t0) / calls
        except Exception as e:  # noqa: BLE001 - report every shape, then fail
            failures.append(f"fewrow_grouped {name}: the compiler refused "
                            f"it: {type(e).__name__}: {str(e)[:1500]}")
            continue
        if not worst <= GROUPED_ERR_LIMIT:
            failures.append(
                f"fewrow_grouped {name}: rel err {worst:.3g} against "
                f"jnp.dot at the highest precision (limit "
                f"{GROUPED_ERR_LIMIT:.3g})")
        moved = live * count * k * n * jnp.dtype(dtype).itemsize
        row = {"name": name, "leaf": [layers, held, k, n], "leaves": count,
               "slots": slots, "live": live,
               "rel_err": float(f"{worst:.3g}")}
        if seconds:
            row.update(us_a_call=round(seconds * 1e6, 1),
                       gb_per_s=round(moved / seconds / 1e9, 1))
        rows.append(row)
        say(f"fewrow_grouped {name}: {live} of {slots} slots, rel err "
            f"{worst:.3g}" + (
                f", {moved / 1e6:.1f} MB in {seconds * 1e6:.1f} us = "
                f"{moved / seconds / 1e9:.1f} GB/s" if seconds else ""))
    return rows


def child_report(cfg: dict, out_dir: str, env: dict, what: str, cmd: list,
                 timeouts: float = 1.0) -> tuple:
    """A child that owns the device while it lives and prints its report
    as its last line: ``(exit code, report, the end of its stderr)``,
    the stderr kept in ``<out>/<what>.stderr.log``."""
    log_path = os.path.join(out_dir, f"{what}.stderr.log")
    with open(log_path, "wb") as log:
        proc = subprocess.run(
            cmd + (["--rehearse"] if cfg["rehearsal"] else []), cwd=out_dir,
            env=env, stdout=subprocess.PIPE, stderr=log,
            timeout=timeouts * cfg["first_timeout"])
    with open(log_path, "rb") as f:
        tail = f.read()[-3000:].decode("utf-8", "replace")
    lines = proc.stdout.decode().strip().splitlines()
    check(bool(lines), f"the {what} child printed nothing (exit "
                       f"{proc.returncode}):\n{tail}")
    return proc.returncode, json.loads(lines[-1]), tail


def kernel_phase(cfg: dict, out_dir: str, env: dict, result: dict) -> None:
    code, report, tail = child_report(
        cfg, out_dir, env, "kernels",
        [sys.executable, os.path.abspath(__file__), "--kernel-child"])
    check(code == 0, f"kernel child exited with code {code}:\n{tail}")
    if result["device"] is None:     # a kernels-only run
        result["device"] = report["device"]
    result["smoke_facts"]["pallas_flash_attention"] = {
        "interpret": report["interpret"], "shapes": report["shapes"]}
    result["smoke_facts"]["pallas_geglu"] = {
        "interpret": report["interpret"], "limit": GEGLU_ERR_LIMIT,
        "shapes": report["geglu_shapes"]}
    result["smoke_facts"]["pallas_fewrow_grouped"] = {
        "interpret": report["interpret"], "limit": GROUPED_ERR_LIMIT,
        "shapes": report["grouped_shapes"]}
    say(f"kernels: {len(report['shapes'])} shape(s) within "
        f"{KERNEL_ERR_RATIO} x xla_attention's error against fp32; "
        f"{len(report['geglu_shapes'])} GEGLU shape(s) within "
        f"{GEGLU_ERR_LIMIT} of fp32; {len(report['grouped_shapes'])} "
        f"grouped few-row shape(s) within {GROUPED_ERR_LIMIT:.3g} of "
        f"jnp.dot")


# --- the language model against its reference --------------------------------

def verify_child(cfg: dict, out_dir: str, env: dict, script: str,
                 args: list, failed: str) -> dict:
    """``benchmarks/chip/<script>.py`` as a child (a server of its own,
    then the plain reference): its report, checked ``ok``."""
    code, report, tail = child_report(
        cfg, out_dir, env, script,
        [sys.executable,
         os.path.join(HERE, "benchmarks", "chip", f"{script}.py"), *args,
         "--out", os.path.join(out_dir, script)], 3)
    check(code == 0 and report["ok"],
          f"{failed}: {json.dumps(report)}\n{tail}")
    return report


def lm_phase(cfg: dict, out_dir: str, env: dict, result: dict) -> None:
    """``benchmarks/chip/verify_lm.py`` as a child: one request of the
    prompt expander's graph at the timed size, then the reference."""
    report = verify_child(
        cfg, out_dir, env, "verify_lm", ["--requests", "1"],
        "the served logits are outside a limit, or an 8-bit reading is "
        "inside all of them")
    if result["device"] is None:     # an lm-only run
        dev = report["device"]
        result["device"] = {"platform": dev["platform"], "kind": dev["kind"],
                            "count": 1}
    served = report["served"][0]
    result["smoke_facts"]["language_model"] = {
        key: served[key] for key in
        ("positions", "prompt_ids", "max_over_std", "mean_over_std",
         "margin_over_std", "argmax_agree", "limits")} | {
        "weights_8bit": report["weights_8bit"]["mean_over_std"],
        "cache_8bit": report["cache_8bit"]["mean_over_std"]}
    say(f"lm: {served['positions']} positions within the limits "
        f"(mean {served['mean_over_std']:.4f}, max "
        f"{served['max_over_std']:.4f} of a standard deviation)")


def lm_ssm_phase(cfg: dict, out_dir: str, env: dict, result: dict) -> None:
    """``benchmarks/chip/verify_lm_ssm.py`` as a child: the decoder of
    state-space and attention layers (granite-4.0-h-micro) behind its
    2048-position prompt, one request alone and four of unequal length as
    the rows of one execution, each held to the plain float32 reference
    (the recurrence position by position); a bf16 state, an 8-bit cache,
    8-bit weights and a dropped ``D`` skip each have to fail."""
    report = verify_child(
        cfg, out_dir, env, "verify_lm_ssm", [],
        "a served row is outside a limit, or a reading that has to fail "
        "is inside all of them")
    worst = max(report["served"], key=lambda r: r["mean_over_std"])
    result["smoke_facts"]["language_model_ssm"] = {
        "rows": len(report["served"]), "together": report["together"],
        **{key: worst[key] for key in ("positions", "max_over_std",
                                       "mean_over_std", "limits")},
        **{key: report[key]["mean_over_std"]
           for key in ("state_bf16", "cache_8bit", "weights_8bit",
                       "skip_dropped")}}
    say(f"lm (state-space): {len(report['served'])} rows within the "
        f"limits (worst mean {worst['mean_over_std']:.4f} of a standard "
        f"deviation; a bf16 state reads "
        f"{report['state_bf16']['mean_over_std']:.4f})")


def lm_dsa_phase(cfg: dict, out_dir: str, env: dict, result: dict) -> None:
    """``benchmarks/chip/verify_lm_dsa_moe.py`` as a child: the decoder
    with a learned key selection and routed experts (Keye-VL-2.0-30B-A3B's
    language model, one pipeline stage) behind its 8192-position prompt,
    one request alone and four as the rows of one execution, each held to
    the plain float32 reference under the program's expert choices and key
    selections; 8-bit caches, 8-bit weights and six breakages of the
    mechanism each have to fail."""
    report = verify_child(
        cfg, out_dir, env, "verify_lm_dsa_moe", [],
        "a served row is outside a limit, or a reading that has to fail "
        "is inside all of them")
    worst = max(report["served"], key=lambda r: r["mean_over_std"])
    result["smoke_facts"]["language_model_dsa"] = {
        "rows": len(report["served"]), "together": report["together"],
        **{key: worst[key] for key in (
            "positions", "max_over_std", "mean_over_std", "limits",
            "selection_agree", "selection_worst_margin", "free")},
        **{key: report[key]["mean_over_std"]
           for key in ("cache_8bit", "weights_8bit", "no_selection",
                       "last_topk", "no_relu", "no_head_weights",
                       "top7_of_8", "no_renormalisation")}}
    say(f"lm (selected keys): {len(report['served'])} rows within the "
        f"limits (worst mean {worst['mean_over_std']:.4f} of a standard "
        f"deviation; the program selects {worst['selection_agree']:.4f} "
        f"of the reference's keys; 8-bit caches read "
        f"{report['cache_8bit']['mean_over_std']:.4f})")


# --- rows started from a prefix's snapshot --------------------------------------

# the user's words of the four rows: prompts of unequal length behind the
# same instructions (the last is one word: fewer ids than the convolution
# has taps of tail only where the template is cut too, which the CPU
# tests hold; here the rows differ in their padding)
PREFIX_ROW_WORDS = (12, 3, 20, 1)
PREFIX_SEED = 4100000041
# a row's state behind the prompt may differ from the reference's, in the
# mean, by at most this many times what the full path's does (the path
# it replaces: `KERNEL_ERR_RATIO`'s rule)
STATE_ERR_RATIO = 1.25
STATE_ERR_FLOOR = 1e-5      # the float32 rehearsal: both are a few ulps


def lm_prefix_child(rehearse: bool) -> int:
    """Runs in its own process (it owns the chip while it lives).  The
    cell's configuration (granite-4.0-h-micro at its published widths,
    the operator's 1,950 instruction ids, a 2048-position prompt, 64
    greedy tokens; the rehearsal's tiny sizes with ``rehearse``), four
    rows of unequal length:

    * SERVED: `LanguageModel.generate_rows`, which finds the shared
      prefix, makes its snapshot and runs the program that prefills the
      ids behind it;
    * the FULL path: ``lm_generate`` over the same four whole prompts;
    * the plain float32 REFERENCE of each row's whole prompt, block by
      block (``benchmarks/chip/reference/ssm_hybrid.py``), teacher-forced
      over the served ids for the logits and over the prompt alone for
      the state behind it.

    Held: each served row's logits to the reference inside
    ``verify_lm_ssm.py``'s limits (its greedy ids by the margin among
    them); served against full path inside the same limits: the logits
    at every step up to the first id that differs (greedy ids part
    where a margin is under bf16's rounding, and the logits behind that
    step are of other sequences), the tail and the keys behind the
    prompt.  The recurrent STATE behind the prompt is held by its mean
    error: against the reference inside the limit and at most
    STATE_ERR_RATIO times what the full path's own state reads there,
    and against the full path inside the limit.  Its largest error is
    reported and not held: the state is heavy-tailed (most of its 18.9 M
    values a row lie near 0, a few are hundreds of standard deviations
    out), the accepted full path itself reads 5 to 8 standard deviations
    at its worst element (my chip run, PR 41), and what a wrong state
    does to a request is in the 64 steps of logits held above.  Prints
    one JSON line; exit code 0 only if all of it holds."""
    bench = os.path.join(HERE, "benchmarks", "chip")
    sys.path[:0] = [HERE, bench]
    if rehearse:
        os.environ["DTPU_DEFAULT_FAMILY"] = "tiny"
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    import run as chipbench
    import verify_lm_ssm as verify
    from reference import ssm_hybrid as ref

    from comfyui_distributed_tpu.models import registry, ssm_hybrid
    from comfyui_distributed_tpu.runtime.manager import \
        enable_persistent_compile_cache
    from comfyui_distributed_tpu.utils import trace

    device = jax.devices()[0]
    if device.platform != ("cpu" if rehearse else "tpu"):
        raise SystemExit(f"lm prefix phase: platform is {device.platform!r}")
    enable_persistent_compile_cache()
    config = chipbench.load_json(os.path.join(
        bench, "configs", "granite-4.0-h-micro-expand-sd15-512.json"))
    if rehearse:
        config = chipbench.rehearsal_config(config)
    nodes = {n["class_type"]: n["inputs"] for n in config["graph"].values()}
    node = nodes["LanguageModelGenerate"]
    P, N = node["prompt_tokens"], node["max_new_tokens"]
    model = registry.load_language_model(
        nodes["LanguageModelLoader"]["model_name"])
    cfg, params = model.cfg, model.params
    fp32 = cfg.dtype == jnp.float32
    limits = verify.LIMITS_FP32 if fp32 else verify.LIMITS
    lm = {k: v for k, v in dataclasses.asdict(cfg).items()
          if k not in ("dtype", "state_dtype")} if rehearse else config["lm"]

    with open(os.path.join(bench, "traffic", "words.txt"),
              encoding="utf-8") as f:
        words = [w.strip() for w in f if w.strip()]
    rng = np.random.default_rng(PREFIX_SEED)
    rows = [registry.LMRow(" ".join(rng.choice(words, n)), seed=i,
                           instructions=node["instructions"])
            for i, n in enumerate(PREFIX_ROW_WORDS)]
    ids = [model.prompt_ids(r.text, P, r.instructions) for r in rows]
    prefix = model.shared_prefix(rows, P, ids)
    if prefix is None:
        raise SystemExit("lm prefix phase: the rule found no shared prefix")
    K, B = len(prefix), len(rows)

    before = trace.GLOBAL_COUNTERS.snapshot()
    t0 = time.monotonic()
    served = model.generate_rows(rows, N, P)
    served_s = time.monotonic() - t0
    counted = {k: v - before.get(k, 0)
               for k, v in trace.GLOBAL_COUNTERS.snapshot().items()
               if k.startswith("lm.") and v != before.get(k, 0)}
    out0 = served[0][1]
    tokens, logits = np.asarray(out0.tokens), np.asarray(out0.logits)

    # the full path over the same whole prompts, and both paths' state
    # behind the prompt
    def buffer(held):
        padded = np.full((B, P - held), model.tokenizer.pad_id, np.int32)
        for b, i in enumerate(ids):
            padded[b, :len(i) - held] = i[held:]
        return padded, np.asarray([len(i) - held for i in ids], np.int32)

    full_tokens, full_logits, _, _ = ssm_hybrid.make_program(cfg, N)(
        params, *buffer(0), np.arange(B, dtype=np.uint32),
        np.zeros(B, np.float32))
    full_tokens, full_logits = map(np.asarray, (full_tokens, full_logits))
    snapshot = model._snapshot(prefix)
    behind = jax.jit(lambda p, i, n, s=None: ssm_hybrid.prefill(
        cfg, p, i, n, P + N, s)[1])
    state = jax.tree_util.tree_map(np.asarray,
                                   behind(params, *buffer(K), snapshot))
    full_state = jax.tree_util.tree_map(np.asarray,
                                        behind(params, *buffer(0)))

    @functools.partial(jax.jit, static_argnums=(0,))
    def block(kind, stack, l, x):
        lp = {name: ref.f32(jax.lax.dynamic_index_in_dim(
            leaf, l, keepdims=False)) for name, leaf in stack.items()}
        return ref.block(lm, kind, lp, x)

    def reference_state(row_ids):
        """The Mamba layers' states behind ``row_ids``' last id."""
        table = jax.jit(ref.f32)(params["embed_tokens"])
        x, at, states = ref.embed(lm, table, row_ids), {}, []
        for kind in lm["layer_types"]:
            stack = params[ssm_hybrid.STACKS[kind]]
            x, last = block(kind, stack, jnp.int32(at.get(kind, 0)), x)
            at[kind] = at.get(kind, 0) + 1
            if last is not None:
                states.append(np.asarray(last))
        return np.stack(states)

    def over_std(got, want, held=("max_over_std", "mean_over_std")):
        std = float(np.std(want))
        diff = np.abs(np.asarray(got, np.float64) - want)
        reading = {"max_over_std": float(diff.max()) / std,
                   "mean_over_std": float(diff.mean()) / std,
                   "max_over_max": float(diff.max())
                   / float(np.abs(want).max())}
        reading["correct"] = all(reading[k] <= limits[k] for k in held)
        return reading

    mean_only = ("mean_over_std",)

    report = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "prefix_ids": K, "prompt_tokens": P, "new_tokens": N,
              "served_s": served_s, "counted": counted, "limits": limits,
              "rows": []}
    for b in range(B):
        n = len(ids[b])
        row = {"prompt_ids": n, "own_ids": n - K}
        this = {"prompt_ids": ids[b], "tokens": tokens[b],
                "logits": logits[b]}
        teacher, at = verify.rows_of(this)
        want = np.asarray(verify.reference_logits(lm, params, teacher, at))
        row["against_reference"] = verify.compare_logits(
            logits[b], want, tokens[b], limits)
        want_state = reference_state(ids[b])
        row["state_against_reference"] = over_std(
            state["ssm"][:, b], want_state, mean_only)
        row["full_path_state_against_reference"] = over_std(
            full_state["ssm"][:, b], want_state, mean_only)
        row["state_against_reference"]["correct"] &= (
            row["state_against_reference"]["mean_over_std"] <= max(
                STATE_ERR_RATIO * row["full_path_state_against_reference"][
                    "mean_over_std"], STATE_ERR_FLOOR))
        # served against the full path: alike as far as their ids are
        differ = np.flatnonzero(tokens[b] != full_tokens[b])
        alike = int(differ[0]) + 1 if len(differ) else N
        first = P - n            # the full path's cache: padding | prompt
        row["against_full_path"] = {
            "ids_alike": int((tokens[b] == full_tokens[b]).sum()),
            "steps_compared": alike,
            "logits": over_std(logits[b, :alike], full_logits[b, :alike]),
            "ssm": over_std(state["ssm"][:, b], full_state["ssm"][:, b],
                            mean_only),
            "conv": over_std(state["conv"][:, b].astype(np.float32),
                             full_state["conv"][:, b].astype(np.float32)),
            "keys": over_std(
                state["keys"][:, b, first:P].astype(np.float32),
                full_state["keys"][:, b, first:P].astype(np.float32))}
        row["correct"] = row["against_reference"]["correct"] \
            and row["state_against_reference"]["correct"] \
            and all(r["correct"] for r in row["against_full_path"].values()
                    if isinstance(r, dict))
        report["rows"].append(row)
    want = {"lm.prefix_hits": B, "lm.prefix_misses": 1,
            "lm.prefix_positions_served": B * K,
            "lm.prefill_positions": B * (P - K)}
    report["ok"] = all(r["correct"] for r in report["rows"]) \
        and {k: counted.get(k) for k in want} == want
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


def lm_prefix_phase(cfg: dict, out_dir: str, env: dict, result: dict) -> None:
    code, report, tail = child_report(
        cfg, out_dir, env, "lm_prefix",
        [sys.executable, os.path.abspath(__file__), "--lm-prefix-child"], 2)
    with open(os.path.join(out_dir, "lm_prefix.json"), "w",
              encoding="utf-8") as f:
        json.dump(report, f, indent=1)
    check(code == 0 and report["ok"],
          f"a row started from the prefix's snapshot is outside a limit, "
          f"or the counters are not the snapshot path's: "
          f"{json.dumps(report)}\n{tail}")
    if result["device"] is None:
        result["device"] = {**report["device"], "count": 1}
    worst = max(report["rows"],
                key=lambda r: r["against_reference"]["mean_over_std"])
    result["smoke_facts"]["language_model_ssm_prefix"] = {
        key: report[key] for key in ("prefix_ids", "prompt_tokens",
                                     "new_tokens", "counted", "limits")} | {
        "rows": [{k: r[k] for k in ("own_ids", "against_reference",
                                    "state_against_reference",
                                    "full_path_state_against_reference",
                                    "against_full_path")}
                 for r in report["rows"]]}
    say(f"lm (state-space, from a snapshot of {report['prefix_ids']} ids): "
        f"{len(report['rows'])} rows within the limits against the "
        f"reference of the whole prompt (worst mean "
        f"{worst['against_reference']['mean_over_std']:.4f}) and against "
        f"the full path")


LM_TOGETHER = 4      # requests sent together: one execution's rows
LM_SEED = 2800000033


def lm_together_phase(cfg: dict, out_dir: str, env: dict,
                      result: dict) -> None:
    """The batched served path against the reference.  A plain SD1.5
    request holds the executor (it is the cold one: weights, compiles)
    while LM_TOGETHER requests of the prompt expander's graph queue
    behind it, each with ``verify_lm.py``'s save node; the first of them
    then leads the others through one execution of ``lm_generate``.
    Each row's logits go through ``verify_lm.py --compare`` (its
    functions and its limits, in a child: the reference needs the chip
    the server has given back)."""
    bench = os.path.join(HERE, "benchmarks", "chip")
    sys.path.insert(0, bench)
    import run as chipbench
    import verify_lm
    from lib.traffic import Traffic
    config = chipbench.load_json(os.path.join(
        bench, "configs", "ouro-2.6b-expand-sd15-512.json"))
    if cfg["rehearsal"]:
        config = chipbench.rehearsal_config(config)
    prefixes = [f"together_{i}" for i in range(LM_TOGETHER)]
    graphs = []
    for i, prefix in enumerate(prefixes):
        # texts of 6, 9, 12, 15 words: rows of different real lengths
        req = Traffic({"loop": "closed", "text_words": 6 + 3 * i},
                      config["name"], LM_SEED + i).next_request()
        graphs.append(verify_lm.verify_graph(config, req["text"],
                                             req["seed"], prefix))
    (gen,) = [nid for nid, node in graphs[0].items()
              if node["class_type"] == "LanguageModelGenerate"]
    holder = {nid: json.loads(json.dumps(node))
              for nid, node in graphs[0].items()
              if not node["class_type"].startswith(("LanguageModel",
                                                    "SaveLanguageModel"))}
    for node in holder.values():
        for name, value in node["inputs"].items():
            if value == [gen, 0]:
                node["inputs"][name] = "a plain request that holds the queue"
    server = Server(os.path.join(out_dir, "lm_together"),
                    {**env, "DTPU_MESH_SHAPE": "data=1"})
    try:
        server.wait_ready()
        pids = [post_json(f"{server.base}/prompt",
                          {"prompt": g, "client_id": "chip_smoke"}
                          )["prompt_id"] for g in [holder] + graphs]
        deadline = time.monotonic() + 2 * cfg["first_timeout"]
        while True:
            server.require_alive()
            hist = get_json(f"{server.base}/history")
            if all(p in hist for p in pids):
                break
            check(time.monotonic() < deadline,
                  f"the requests sent together never finished:\n"
                  f"{server.log_tail()}")
            time.sleep(0.25)
        check(all(hist[p].get("status") == "success" for p in pids),
              f"a request sent together ended "
              f"{[hist[p] for p in pids]}:\n{server.log_tail()}")
        counters = server.metrics()["pipeline"]["counters"]
        rc = server.shut_down()
        check(rc == 0, f"server child exited with code {rc}")
    finally:
        server.kill()
    shared = {k: counters.get(f"lm.{k}", 0) for k in
              ("executions", "rows", "padded_rows", "followers_served",
               "followers_dropped")}
    check(shared == {"executions": 1, "rows": LM_TOGETHER, "padded_rows": 0,
                     "followers_served": LM_TOGETHER - 1,
                     "followers_dropped": 0},
          f"{LM_TOGETHER} requests sent together did not run as one "
          f"execution: {shared}")
    paths = [os.path.join(server.cwd, "output", f"{p}.npz")
             for p in prefixes]
    cmd = [sys.executable, os.path.join(bench, "verify_lm.py"),
           "--compare", *paths] + (["--rehearse"] if cfg["rehearsal"]
                                   else [])
    proc = subprocess.run(cmd, cwd=HERE, env=env, capture_output=True,
                          text=True, timeout=2 * cfg["first_timeout"])
    lines = proc.stdout.strip().splitlines()
    check(proc.returncode == 0 and bool(lines),
          f"verify_lm --compare failed (exit {proc.returncode}):\n"
          f"{proc.stderr[-3000:]}")
    report = json.loads(lines[-1])
    check(report["ok"] and len(report["served"]) == LM_TOGETHER,
          f"a row of the shared execution is outside a limit: "
          f"{json.dumps(report)}")
    result["smoke_facts"]["language_model_together"] = shared | {
        "served": [{key: row[key] for key in
                    ("prompt_ids", "positions", "max_over_std",
                     "mean_over_std", "margin_over_std", "argmax_agree")}
                   for row in report["served"]],
        "limits": report["served"][0]["limits"]}
    say(f"lm: {LM_TOGETHER} requests as the rows of one execution, each "
        f"within the limits (mean "
        f"{[round(r['mean_over_std'], 5) for r in report['served']]}, max "
        f"{[round(r['max_over_std'], 4) for r in report['served']]} of a "
        f"standard deviation)")


# --- main --------------------------------------------------------------------

def configuration(rehearse: bool) -> dict:
    if rehearse:
        # tiny family: its VAE scales by 2 where the latent rule divides
        # by 8, so a 64 px request decodes to 16 px
        return {"rehearsal": True, "platform": "cpu", "sdxl_px": 64,
                "sdxl_out_px": 16, "steps": 2, "upscale_in_px": 32,
                "upscale_out_px": 128, "first_timeout": 600.0,
                "steady_timeout": 120.0, "monitor_interval_s": 0.0}
    return {"rehearsal": False, "platform": "tpu", "sdxl_out_px": 1024,
            "upscale_in_px": 512, "upscale_out_px": 2048,
            "first_timeout": 900.0, "steady_timeout": 300.0,
            "monitor_interval_s": 5.0}


def child_env(rehearse: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["DTPU_DEFAULT_FAMILY"] = "tiny"
    else:
        # full width or nothing: never a family override on the chip
        env.pop("DTPU_DEFAULT_FAMILY", None)
    return env


def versions() -> dict:
    out = {}
    for dist in ("jax", "jaxlib", "libtpu", "flax", "numpy"):
        try:
            out[dist] = importlib.metadata.version(dist)
        except importlib.metadata.PackageNotFoundError:
            out[dist] = None
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny family, small sizes")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"), help="output directory")
    ap.add_argument("--phases", default=",".join(DEFAULT_PHASES),
                    help=f"comma-separated subset of {','.join(PHASES)}")
    ap.add_argument("--kernel-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--lm-prefix-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.kernel_child:
        return kernel_child(args.rehearse)
    if args.lm_prefix_child:
        return lm_prefix_child(args.rehearse)

    phases = [p for p in args.phases.split(",") if p]
    for missing in ("comfyui_distributed_tpu", "workflows"):
        if not os.path.isdir(os.path.join(HERE, missing)):
            print(f"chip_smoke: {missing}/ is not next to this script; "
                  f"it drives the repo it lives in", file=sys.stderr)
            return 2
    if not set(phases) <= set(PHASES) or not phases:
        print(f"chip_smoke: --phases takes a subset of {PHASES}",
              file=sys.stderr)
        return 2
    if not args.rehearse and (os.environ.get("JAX_PLATFORMS") or ""
                              ).strip().lower() == "cpu":
        print("chip_smoke: JAX_PLATFORMS=cpu holds JAX to the CPU here; "
              "this is the chip check and it does not fall back "
              "(--rehearse is the CPU rehearsal)", file=sys.stderr)
        return 2

    cfg = configuration(args.rehearse)
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)
    env = child_env(args.rehearse)
    summary = {"device": None, "mesh_axes": None,
               "rehearsal": args.rehearse, "phases": phases,
               "smoke_facts": {"versions": versions()}}
    t0 = time.monotonic()
    try:
        if "sdxl" in phases or "upscale" in phases:
            server_phases(phases, cfg, out_dir, env, summary)
        if "kernels" in phases:
            kernel_phase(cfg, out_dir, env, summary)
        if "lm" in phases:
            lm_phase(cfg, out_dir, env, summary)
            lm_together_phase(cfg, out_dir, env, summary)
        if "lm" in phases or "lm_ssm" in phases:
            lm_ssm_phase(cfg, out_dir, env, summary)
            lm_prefix_phase(cfg, out_dir, env, summary)
        if "lm" in phases or "lm_dsa" in phases:
            lm_dsa_phase(cfg, out_dir, env, summary)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    summary["smoke_facts"]["total_wall_s"] = round(time.monotonic() - t0, 1)
    summary["claim"] = None
    with open(os.path.join(out_dir, "summary.json"), "w",
              encoding="utf-8") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary), flush=True)
    # the result: the verdict and the device, nothing else, last
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
