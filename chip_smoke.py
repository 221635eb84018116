"""On-chip smoke run of the DeltaState plane.

Drives the main path once through the entry points a user calls: olmo-1b at
its published widths (random bf16 weights from ``--seed``) served by the
paged ``Engine``, one agent session checkpointed and rolled back by
``DeltaCR`` under a ``StateManager``, and a few MCTS iterations on top.

    python chip_smoke.py              # one chip: the session path
    python chip_smoke.py --chips 4    # four chips: sharded dumps only

Checks, any of which fails the run with a non-zero exit:

* the native ``paged_attention`` kernel agrees with ``kernels.ref`` on the
  live pool, and the compiled decode step and dump encode hold Mosaic
  kernels (``tpu_custom_call``);
* a fast (template fork) and a slow (image + ``delta_apply``) rollback
  each continue with the same greedy tokens as the run that never rolled
  back;
* ``DeltaCR.health()``: no fallback dump, not degraded, no fused checksum
  mismatch, every image landed as ``delta`` or ``copy``.

``--chips 4`` instead dumps a 1 GiB bf16 state sharded on a (2, 2)
("data", "model") mesh and checks zero gathers, fetches only from the
devices that own dirty tiles, chunk digests equal to the one-device dump of
the same array, and a restore onto a (4, 1) mesh equal to the source.

The run refuses to start off the TPU, or with ``REPRO_FORCE_REF`` /
``REPRO_INTERPRET`` set.  The lines before the last are one unrepeated
smoke reading, not a benchmark; the last line is the JSON verdict.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def assert_native(hlo_text: str, what: str) -> None:
    check("tpu_custom_call" in hlo_text, f"{what}: no Mosaic kernel in the compiled program")


def require_tpu(n_chips: int):
    for var in ("REPRO_FORCE_REF", "REPRO_INTERPRET"):
        if os.environ.get(var):
            raise SmokeFailure(f"{var} is set: the smoke run must take the native kernels")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < n_chips:
        raise SmokeFailure(f"--chips {n_chips}: JAX found {len(devices)} devices")
    return devices


# ---------------------------------------------------------------- one chip
def session_path(
    arch: str = "olmo-1b",
    *,
    seed: int = 0,
    num_pages: int = 2048,
    page_size: int = 16,
    max_pages: int = 64,
    prompt_len: int = 512,
) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import DeltaCR, DeltaFS, Sandbox, StateManager
    from repro.kernels import ops, ref
    from repro.models import Model
    from repro.search import MCTS, MCTSConfig
    from repro.search.decode_task import DecodeSearchTask
    from repro.serve import Engine, PagePool, PagedSession, SamplingParams

    cfg = get_config(arch)
    model = Model(cfg)
    t = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(seed)))
    n_params = sum(int(p.size) for p in jax.tree.leaves(params))
    dtypes = sorted({str(p.dtype) for p in jax.tree.leaves(params)})
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{n_params} params {dtypes}, init {time.perf_counter() - t:.2f} s")
    check(dtypes == [cfg.param_dtype], f"params in {dtypes}, config says {cfg.param_dtype}")
    pool = PagePool(cfg, num_pages=num_pages, page_size=page_size,
                    max_pages_per_session=max_pages)
    log(f"page pool: {num_pages} pages of {page_size} tokens, "
        f"{pool.bytes_per_page() * num_pages / 2**30:.2f} GiB of KV")
    engine = Engine(model, params, pool)

    # ---- prefill one prompt, fork four sessions, decode in a batch
    rng = np.random.default_rng(seed)
    prompt = [int(x) for x in rng.integers(0, cfg.vocab_size, size=prompt_len)]
    t = time.perf_counter()
    root = engine.new_session(prompt, SamplingParams(temperature=0.0))
    log(f"prefill {prompt_len} tokens, first call (compile included): "
        f"{time.perf_counter() - t:.2f} s")
    sessions = [root.fork() for _ in range(4)]
    root.release()
    for i, s in enumerate(sessions[1:], start=1):
        s.extras["temperature"] = np.asarray([0.8], np.float32)
        s.extras["rng_seed"] = np.asarray([seed + i], np.int64)
    t = time.perf_counter()
    engine.step(sessions)
    log(f"decode step, batch 4, first call (compile included): {time.perf_counter() - t:.2f} s")
    t = time.perf_counter()
    for _ in range(31):
        engine.step(sessions)
    dt = time.perf_counter() - t
    log(f"decode: 31 batched steps x 4 sessions in {dt:.3f} s = {31 * 4 / dt:.1f} tokens/s")

    # ---- kernels against the reference, on the live pool
    pk = pool.pools_k["stage0"]["l0_s0_attn"][0]
    pv = pool.pools_v["stage0"]["l0_s0_attn"][0]
    table = jnp.asarray(np.stack([s.table for s in sessions]), jnp.int32)
    lens = jnp.asarray([s.seq_len for s in sessions], jnp.int32)
    q = jax.random.normal(
        jax.random.PRNGKey(seed + 1), (4, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim)
    ).astype(pk.dtype)
    got = np.asarray(ops.paged_attention(q, pk, pv, table, lens), np.float32)
    want = np.asarray(ref.paged_attention_ref(q, pk, pv, table, lens), np.float32)
    err = float(np.max(np.abs(got - want)))
    check(np.all(np.isfinite(got)), "paged_attention: non-finite output")
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
    log(f"paged_attention vs ref on the live pool: max abs err {err:.3e} (bf16 tol 2e-2)")
    del pk, pv
    # shapes only: holding the live cache would pin this step's pool arrays
    # (4 GiB of KV) after later steps replace them
    with pool.lock:
        cache = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), engine._build_cache(sessions)
        )
    decode_hlo = (
        jax.jit(model.decode_step)
        .lower(params, jnp.asarray([s.tokens[-1] for s in sessions], jnp.int32), cache)
        .compile()
        .as_text()
    )
    assert_native(decode_hlo, "decode step")

    # ---- the agent session under a StateManager
    for s in sessions[1:]:
        s.release()
    sess = sessions[0]
    fs = DeltaFS(chunk_bytes=64 * 1024)
    fs.write("repo/prompt", np.asarray(prompt, np.int32))
    cr = DeltaCR(
        store=fs.store,
        restore_fn=lambda p: PagedSession.restore_from_payload(pool, p),
    )
    sm = StateManager(Sandbox(fs, sess), cr)

    def greedy(n: int):
        return [engine.step([sm.sandbox.proc])[0] for _ in range(n)]

    t = time.perf_counter()
    c1 = sm.checkpoint()
    log(f"checkpoint 1 (template fork + dump submit): {(time.perf_counter() - t) * 1e3:.3f} ms")
    greedy(16)
    t = time.perf_counter()
    c2 = sm.checkpoint()
    log(f"checkpoint 2: {(time.perf_counter() - t) * 1e3:.3f} ms")
    cr.wait_dumps()
    img1, img2 = cr.dump_future(c1).result(), cr.dump_future(c2).result()
    log(f"dump 1: mode {img1.mode}, {img1.dump_bytes} bytes, {img1.wall_ms:.3f} ms; "
        f"dump 2: mode {img2.mode}, {img2.dirtied_chunks} dirty chunks, "
        f"{img2.dump_bytes} bytes, {img2.wall_ms:.3f} ms")
    check(img2.mode == "delta", f"dump 2 landed as {img2.mode}, not through the delta kernel")
    check(cr.stats.kernel_keys > 0, "no tensor was diffed by the dump kernel")
    n_rows = 1 << (sm.sandbox.proc.n_pages - 1).bit_length()
    row_bytes = pool.bytes_per_page() // 2
    grid = jax.ShapeDtypeStruct((n_rows, row_bytes), jnp.uint8)
    assert_native(
        ops.fused_encode.lower(grid, grid, max(1, n_rows // 2)).compile().as_text(),
        "dump encode",
    )
    ref_tokens = greedy(16)

    t = time.perf_counter()
    how = sm.restore(c2)
    log(f"rollback (fast path, template fork): {(time.perf_counter() - t) * 1e3:.3f} ms")
    check(how == "fast", f"expected the fast path, got {how}")
    check(greedy(16) == ref_tokens, "fast rollback: tokens differ from the run that never rolled back")
    check(cr.evict_template(c2), "no template to drop")
    t = time.perf_counter()
    how = sm.restore(c2)
    log(f"rollback (slow path, image + delta_apply): {(time.perf_counter() - t) * 1e3:.3f} ms")
    check(how == "slow", f"expected the slow path, got {how}")
    check(greedy(16) == ref_tokens, "slow rollback: tokens differ from the run that never rolled back")
    log("both rollbacks continue with the same 16 greedy tokens as the unrolled run")

    # ---- a few MCTS iterations on the same StateManager
    task = DecodeSearchTask(engine, k_tokens=4, width=2)
    t = time.perf_counter()
    st = MCTS(sm, task, MCTSConfig(iterations=4, value_isolation=False, seed=seed)).run()
    cr.wait_dumps()
    log(f"MCTS: {st.iterations} iterations, {st.checkpoints} checkpoints, "
        f"{st.restores} restores ({st.fast_restores} fast) in {time.perf_counter() - t:.2f} s")
    check(st.iterations == 4, "MCTS did not run its iterations")

    h = cr.health()
    log(f"DeltaCR health: dumps {h['dumps']}, modes {h['mode_histogram']}, "
        f"fallback {h['fallback_dumps']}, degraded {h['degraded']}, "
        f"fused checksum mismatches {h['fused_checksum_mismatches']}")
    check(h["dump_failures"] == 0, "a dump failed")
    check(h["fallback_dumps"] == 0, "a dump fell back to the legacy path")
    check(h["degraded"] is False, "DeltaCR went degraded")
    check(h["fused_checksum_mismatches"] == 0, "fused checksum mismatch")
    check(set(h["mode_histogram"]) <= {"delta", "copy"}, f"modes {h['mode_histogram']}")
    cr.shutdown()


# -------------------------------------------------------------- four chips
def sharded_path(*, seed: int = 0, shape=(16384, 32768)) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from repro.core import DeltaCR
    from repro.core.policy import DumpPolicy
    from repro.dist import shard_dump as sd
    from repro.launch.mesh import make_local_mesh

    devices = jax.devices()[:4]
    mesh = make_local_mesh(model_parallel=2)
    check(mesh.devices.shape == (2, 2), f"mesh {mesh.devices.shape}, wanted (2, 2)")
    src = NamedSharding(mesh, PartitionSpec("data", "model"))
    dst_mesh = jax.make_mesh((4, 1), ("data", "model"), devices=devices)
    dst = NamedSharding(dst_mesh, PartitionSpec("data", "model"))
    one = SingleDeviceSharding(devices[0])

    w = jax.jit(
        lambda k: jax.random.normal(k, shape, jnp.bfloat16), out_shardings=one
    )(jax.random.PRNGKey(seed))
    # dirty two elements, in the blocks of two different devices
    hit = (np.array([1, shape[0] - 2]), np.array([3, shape[1] - 5]))
    w2 = jax.jit(lambda a: a.at[hit].add(1.0), out_shardings=one)(w)
    w_sh, w2_sh = jax.device_put(w, src), jax.device_put(w2, src)
    placed = {sh.device for sh in w_sh.addressable_shards}
    check(len(placed) == 4, f"source spread over {len(placed)} devices, not 4")
    owners = set()
    for sh in w_sh.addressable_shards:
        rows, cols = sh.index
        for r, c in zip(*hit):
            if rows.start <= r < rows.stop and cols.start <= c < cols.stop:
                owners.add(str(sh.device))
    log(f"state: {shape} bfloat16 = {w.nbytes / 2**30:.2f} GiB on mesh (2, 2) over "
        f"{sorted(str(d) for d in placed)}; dirty tiles owned by {sorted(owners)}")

    def dump_twice(a0, a1, restore_to=None):
        state = sd.ShardedArrayState({"w": a0})
        cr = DeltaCR(
            policy=DumpPolicy(mode="delta"),
            restore_fn=lambda p: sd.ShardedArrayState.restore_from_payload(p, {"w": restore_to}),
        )
        snaps, digests = [], []
        for ck, parent, arr in ((1, None, a0), (2, 1, a1)):
            state.set("w", arr)
            sd.reset_fetch_stats()
            t = time.perf_counter()
            with sd.no_implicit_transfers():
                cr.checkpoint(state, ck, parent, priority="sync")
                cr.wait_dumps()
            wall = time.perf_counter() - t
            snaps.append(sd.fetch_stats())
            img = cr.dump_future(ck).result()
            digests.append(img.entries["w"].digests)
            log(f"  dump {ck}: mode {img.mode}, {img.dirtied_chunks} dirty tiles, "
                f"fetched {snaps[-1]['fetched_bytes']} bytes, gathers "
                f"{snaps[-1]['gather_bytes']} bytes, {wall:.3f} s")
        restored = None
        if restore_to is not None:
            check(cr.evict_template(2), "no template to drop")
            state2, how = cr.restore(2)
            check(how == "slow", f"expected a slow restore, got {how}")
            restored = state2.get("w")
        cr.shutdown()
        return snaps, digests, restored

    log("sharded dumps, mesh (2, 2):")
    snaps, digests, restored = dump_twice(w_sh, w2_sh, restore_to=dst)
    log("one-device dumps of the same array:")
    _, ref_digests, _ = dump_twice(w, w2)

    check(all(s["gather_bytes"] == 0 and s["gathers"] == 0 for s in snaps), "a dump gathered")
    spread = {d for d, b in snaps[0]["by_device"].items() if b}
    check(len(spread) == 4, f"full dump fetched from {len(spread)} devices, not 4")
    fetched = {d for d, b in snaps[1]["by_device"].items() if b}
    check(fetched == owners, f"delta dump fetched from {sorted(fetched)}, dirty owners {sorted(owners)}")
    check(digests == ref_digests, "chunk digests differ from the one-device dump")
    rdevs = {sh.device for sh in restored.addressable_shards}
    check(restored.sharding.is_equivalent_to(dst, len(shape)), "restore not on the (4, 1) mesh")
    check(len(rdevs) == 4, f"restore spread over {len(rdevs)} devices, not 4")
    same = jax.jit(jnp.array_equal)(jax.device_put(restored, one), w2)
    check(bool(jax.device_get(same)), "restore onto (4, 1) differs from the source")
    log(f"zero gather bytes; delta dump fetched {snaps[1]['fetched_bytes']} bytes from "
        f"{sorted(fetched)} only; digests of {len(digests[0])} + {len(digests[1])} chunks equal "
        f"the one-device dump; restore onto mesh (4, 1) equals the source")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devices[0].device_kind} x {len(devices)}; smoke reading: one "
        f"unrepeated run, not a benchmark")
    t = time.perf_counter()
    if args.chips == 4:
        sharded_path(seed=args.seed)
    else:
        session_path(seed=args.seed)
    log(f"smoke wall: {time.perf_counter() - t:.1f} s")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
