#!/usr/bin/env python3
"""Run the system's two main paths once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: stencil phase, then serve phase
    python chip_smoke.py --chips 4   # four chips: the sharded stencil path only

Phase ``stencil`` compiles ``jacobi2d5p`` through ``cfa.compile`` onto the
Pallas backend (compiled kernels, f32), runs it twice (cold, then warm),
checks that the wave kernel lowers to a ``tpu_custom_call`` and compares
every facet array with the untiled reference packed into the same layout.

Phase ``serve`` serves a handful of requests with full-width ``qwen3-0.6b``
(random bf16 weights from ``--seed``) through ``ContinuousBatcher`` and
checks every generated token against a teacher-forced ``lm_forward``.

``--chips 4`` lets the ``distribute`` pass split ``heat3d`` over four
chips (``host_budget``), runs the ``sharded`` backend with compiled kernels,
checks that each chip holds facet arrays, and compares with the reference.

Everything runs in this one process.  Without a TPU the script exits
non-zero before doing anything.  The last line of standard output is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``;
every printed timing is the host's clock around work that ends in
``block_until_ready`` — a smoke timing, not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro import cfa  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.cfa import pack_all  # noqa: E402
from repro.distributed.sharding import port_mesh  # noqa: E402
from repro.kernels.stencil import execute_tiles  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.lm import init_lm, lm_forward  # noqa: E402
from repro.serve.scheduler import ContinuousBatcher, Request  # noqa: E402

HBM_BYTES = 16e9  # one TPU v5e chip
STENCIL_SPACE = (64, 2048, 2048)
STENCIL_TILE = (16, 32, 128)
SHARDED_SPACE = (16, 32, 32, 256)
SHARDED_TILE = (4, 8, 8, 128)
PROMPT_LENS = (128, 256, 512)
N_REQUESTS = 8
NEW_TOKENS = 32
LANES = 4
# bf16 keeps 8 significant bits, so one rounding moves a logit of magnitude
# m by up to m * 2**-8.  The batcher (prefill + decode over a bf16 block
# cache) and the teacher-forced forward (chunked attention) round
# differently at every layer; 2**-4 allows 16 such roundings to pile up at
# the top logit, while a wrong token typically sits several units below it.
LOGIT_TOL_REL = 2.0**-4


def log(msg: str) -> None:
    print(msg, flush=True)


def stencil_tolerance(program, n_planes: int, scale: float) -> float:
    """Largest |facet - reference| allowed in f32.

    The programs checked here (``jacobi2d5p``, ``heat3d``) take a convex
    combination of neighbours (positive coefficients summing to 1), so every
    value stays within ``scale = max|inputs|`` and an error made at one
    plane is carried, not amplified, by the next.  Either side rounds at
    most twice per tap per plane, each time by at most 2**-24 relative,
    however its compiler orders or fuses the arithmetic.  So
    ``n_planes * 4 * taps * 2**-24 * scale`` bounds their difference.
    """
    taps = len(program.deps.vectors)
    return n_planes * 4 * taps * 2.0**-24 * scale


def _facet_bytes(pipe) -> int:
    return sum(math.prod(pipe.facet_shape(k)) for k in pipe.specs) * 4


def _max_facet_error(facets, compiled, inputs) -> float:
    """Max |facet - packed reference| over every facet array (facet_0's
    virtual live-in row is input, not result, and is skipped)."""
    want = pack_all(compiled.reference(inputs), compiled.pipeline.specs)
    err = 0.0
    for k, got in facets.items():
        got = got[1:] if k == 0 else got
        w = jax.device_put(want[k], list(got.devices())[0])
        err = max(err, float(jnp.max(jnp.abs(got - w))))
    return err


def _hbm_filling_space(space, per_tile_bytes_scale, limit):
    """Grow the spatial axes (alternately doubled) while the facet family,
    which scales with the spatial extent at a fixed tile, stays within
    ``limit``; returns (space, scale factor)."""
    space, f, axis = list(space), 1, 1
    while per_tile_bytes_scale * f * 2 <= limit:
        space[axis] *= 2
        f *= 2
        axis = 1 + axis % (len(space) - 1)
    return tuple(space), f


def stencil_phase(space=STENCIL_SPACE, tile=STENCIL_TILE, *, seed: int = 0,
                  program: str = "jacobi2d5p") -> dict:
    """Compile, run cold and warm, lower one wave, compare with the
    reference; returns the facts it printed."""
    compiled = cfa.compile(program, space, layout=tile, target="tpu-v5e-hbm",
                           backend="pallas")
    pipe = compiled.pipeline
    waves = pipe.wavefronts()
    n_tiles = math.prod(pipe.num_tiles)
    fbytes = _facet_bytes(pipe)
    log(f"[stencil] {program} space={space} tile={tile} backend={compiled.backend} "
        f"facet_bytes={fbytes} facet_shapes="
        f"{ {k: pipe.facet_shape(k) for k in pipe.specs} }")
    log(f"[stencil] tiles={n_tiles} waves={len(waves)} "
        f"max_wave={max(map(len, waves))}")
    w0 = pipe.specs[0].width
    inputs = jax.random.normal(jax.random.PRNGKey(seed), (w0, *space[1:]),
                               jnp.float32)

    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        facets = jax.block_until_ready(compiled(inputs))
        times.append(time.perf_counter() - t0)
    cold_s, warm_s = times
    log(f"[stencil] cold_wall_s={cold_s} warm_wall_s={warm_s} "
        f"warm_s_per_tile={warm_s / n_tiles} (host clock, smoke timing)")

    deploy, scale = _hbm_filling_space(space, fbytes, HBM_BYTES)
    log(f"[stencil] cut: an HBM-filling space at this tile is {deploy} "
        f"(~{fbytes * scale} facet bytes; copy_out updates the donated "
        f"facet arrays in place); run at {space} "
        f"because every tile is dispatched from the host (ROADMAP S2): "
        f"~{n_tiles * scale} tiles at {warm_s / n_tiles} s each would take "
        f"~{n_tiles * scale * warm_s / n_tiles} s per sweep")

    hshape = tuple(w + t for w, t in zip(pipe.widths, tile))
    batch = jax.ShapeDtypeStruct((max(map(len, waves)), *hshape), jnp.float32)
    hlo = execute_tiles.lower(program, batch, tuple(tile)).compile().as_text()
    has_kernel = "tpu_custom_call" in hlo
    log(f"[stencil] wave kernel lowered to tpu_custom_call: {has_kernel}")

    err = _max_facet_error(facets, compiled, inputs)
    tol = stencil_tolerance(compiled.program, space[0],
                            float(jnp.max(jnp.abs(inputs))))
    log(f"[stencil] max_abs_error={err} tolerance={tol}")
    if not err <= tol:
        raise AssertionError(f"stencil facets differ from the reference: "
                             f"{err} > {tol}")
    return dict(facet_bytes=fbytes, tiles=n_tiles, waves=len(waves),
                cold_s=cold_s, warm_s=warm_s, max_error=err, tolerance=tol,
                kernel_lowered=has_kernel)


def sharded_phase(space=SHARDED_SPACE, tile=SHARDED_TILE, *, seed: int = 0,
                  n_chips: int = 4, program: str = "heat3d") -> dict:
    """The ``distribute`` pass splits ``program`` over ``n_chips`` ports;
    the sharded backend runs it with the Pallas kernels.  ``heat3d`` (4-D)
    has four facet arrays, so each of four chips owns one."""
    prog = cfa.get_program(program)
    est = cfa.estimate_facet_bytes(
        prog, cfa.IterSpace(space),
        elem_bytes=cfa.get_target("tpu-v5e-hbm").model.elem_bytes)
    budget = -(-est // n_chips)
    compiled = cfa.compile(program, space, layout=tile, target="tpu-v5e-hbm",
                           host_budget=budget)
    log(f"[sharded] {program} space={space} tile={tile} host_budget={budget} "
        f"-> n_ports={compiled.n_ports} backend={compiled.backend} "
        f"distributed={compiled.distributed}")
    if not (compiled.distributed and compiled.n_ports == n_chips
            and compiled.backend == "sharded"):
        raise AssertionError("the distribute pass did not split over "
                             f"{n_chips} ports")
    mesh = port_mesh(compiled.n_ports)
    mesh_devices = set(mesh.devices.flat)
    if len(mesh_devices) != n_chips:
        raise AssertionError(f"port mesh folds {n_chips} ports onto "
                             f"{len(mesh_devices)} device(s)")
    w0 = compiled.pipeline.specs[0].width
    inputs = jax.random.normal(jax.random.PRNGKey(seed), (w0, *space[1:]),
                               jnp.float32)
    t0 = time.perf_counter()
    facets = jax.block_until_ready(compiled(inputs, use_kernel=True, mesh=mesh))
    wall_s = time.perf_counter() - t0
    placement = {k: sorted(d.id for d in arr.devices()) for k, arr in facets.items()}
    held = {d for arr in facets.values() for d in arr.devices()}
    log(f"[sharded] mesh_devices={sorted(d.id for d in mesh_devices)} "
        f"facet_devices={placement} wall_s={wall_s} (host clock, smoke timing)")
    if held != mesh_devices:
        raise AssertionError(f"facets are held by {sorted(d.id for d in held)},"
                             f" not by every mesh device")
    err = _max_facet_error(facets, compiled, inputs)
    tol = stencil_tolerance(prog, space[0], float(jnp.max(jnp.abs(inputs))))
    log(f"[sharded] max_abs_error={err} tolerance={tol}")
    if not err <= tol:
        raise AssertionError(f"sharded facets differ from the reference: "
                             f"{err} > {tol}")
    return dict(n_devices=len(mesh_devices), placement=placement,
                max_error=err, tolerance=tol, wall_s=wall_s)


def serve_phase(cfg=None, *, seed: int = 0, n_requests: int = N_REQUESTS,
                prompt_lens=PROMPT_LENS, new_tokens: int = NEW_TOKENS,
                lanes: int = LANES) -> dict:
    """Serve ``n_requests`` through the continuous batcher and check every
    generated token against a teacher-forced forward pass."""
    if cfg is None:
        cfg = dataclasses.replace(get_config("qwen3-0.6b"),
                                  param_dtype="bfloat16")
    params = init_lm(jax.random.PRNGKey(seed), cfg)
    bs = cfg.kv_block
    max_seq = -(-(max(prompt_lens) + new_tokens) // bs) * bs
    rng = np.random.default_rng(seed)
    reqs = [Request(i, rng.integers(0, cfg.vocab, prompt_lens[i % len(prompt_lens)])
                    .astype(np.int32), new_tokens)
            for i in range(n_requests)]
    batcher = ContinuousBatcher(cfg, params, lanes=lanes, max_seq=max_seq)
    for r in reqs:
        batcher.submit(r)
    t0 = time.perf_counter()
    batcher.run()
    wall_s = time.perf_counter() - t0
    answered = sum(r.done and len(r.out) == new_tokens for r in reqs)
    tokens = sum(len(r.out) for r in reqs)
    log(f"[serve] {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"lanes={lanes} max_seq={max_seq} requests={n_requests} "
        f"answered={answered} tokens={tokens} wall_s={wall_s} "
        f"tokens_per_s={tokens / wall_s} (host clock incl. compiles, smoke "
        f"timing, not a benchmark)")
    if answered != n_requests:
        raise AssertionError(f"{n_requests - answered} request(s) unanswered")

    # teacher forcing: one causal forward over every prompt + generated
    # tokens, right-padded to one length (padding after a position never
    # changes its logits); position L-1+i predicts generated token i
    seqs = [np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
            for r in reqs]
    width = max(map(len, seqs))
    batch = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        batch[i, :len(s)] = s
    fwd = jax.jit(lambda p, t: lm_forward(p, t, cfg, remat=False)[0])
    logits = fwd(params, jnp.asarray(batch))
    worst_gap, worst_tol = 0.0, 0.0
    for i, r in enumerate(reqs):
        L = len(r.prompt)
        rows = logits[i, L - 1:L - 1 + len(r.out), :cfg.vocab].astype(jnp.float32)
        top = np.asarray(jnp.max(rows, axis=-1))
        got = np.asarray(jnp.take_along_axis(
            rows, jnp.asarray(r.out)[:, None], axis=-1))[:, 0]
        gap = top - got
        tol = LOGIT_TOL_REL * np.maximum(np.abs(top), 1.0)
        if (gap > tol).any():
            j = int(np.argmax(gap - tol))
            raise AssertionError(
                f"request {r.rid} token {j}: reference logit {got[j]} is "
                f"{gap[j]} below the top {top[j]} (tolerance {tol[j]})")
        k = int(np.argmax(gap))
        if gap[k] > worst_gap:
            worst_gap, worst_tol = float(gap[k]), float(tol[k])
    log(f"[serve] teacher-forced check: max_top_logit_gap={worst_gap} "
        f"(tolerance there {worst_tol}, {LOGIT_TOL_REL} x max(|top|, 1))")
    return dict(answered=answered, tokens=tokens, wall_s=wall_s,
                max_gap=worst_gap)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded stencil path on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
              f"({dev.device_kind}, {len(devices)} device(s))", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices; "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()  # before the first compile
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} compile_cache={cache_dir}")

    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(seed=args.seed, n_chips=4)
    else:
        facts = stencil_phase(seed=args.seed)
        if not facts["kernel_lowered"]:
            raise AssertionError("the wave kernel did not lower to a "
                                 "tpu_custom_call")
        log(f"[stencil] peak_bytes_in_use="
            f"{dev.memory_stats().get('peak_bytes_in_use')}")
        serve_phase(seed=args.seed)
    log(f"[done] wall_s={time.perf_counter() - t0} peak_bytes_in_use="
        f"{dev.memory_stats().get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
