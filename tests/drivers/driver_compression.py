"""8-way compression driver: compressed psum vs exact psum with
analytic error bounds, wire-dtype HLO checks, error feedback across
steps, and the full compressed training paths (build_train_step and
CompoundRuntime) tracking the uncompressed loss trajectory."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import repro.core.workload as wl
from repro.configs import get_reduced
from repro.core.types import ParallelConfig, ShapeConfig
from repro.dist import sharding as shd
from repro.models import common as cm
from repro.models.model import build_model
from repro.optim import adamw, compression as gcomp
from repro.roofline import analysis as ra
from repro.train import step as step_mod
from repro.launch.mesh import make_mesh

DP, N = 8, 5000
mesh8 = make_mesh((DP,), ("data",))
rng = np.random.default_rng(0)


# ---- compressed psum vs exact psum: bounded elementwise error ------------- #
def reduce_with(method):
    def body(xs):
        x = xs[0]                              # local shard's tensor [N]
        if method == "bf16":
            return gcomp.compressed_psum_bf16(x, "data")
        return gcomp.compressed_psum_int8(x, "data")
    return jax.jit(shd.shard_map(body, mesh8, (P("data"),), P()))


xs = rng.normal(size=(DP, N)).astype(np.float32)
exact = xs.sum(axis=0)

bf = np.asarray(reduce_with("bf16")(jnp.asarray(xs)))
# phase 1: each term rounds to bf16 (2^-8 relative); phase 2: one more
# bf16 rounding of the reduced value
bound_bf = (2.0 ** -8) * np.abs(xs).sum(axis=0) + (2.0 ** -8) * np.abs(exact)
err_bf = np.abs(bf - exact)
assert (err_bf <= bound_bf * 1.05 + 1e-6).all(), \
    (err_bf.max(), bound_bf[err_bf.argmax()])
assert err_bf.max() > 0, "bf16 path suspiciously exact — not compressing?"

q8 = np.asarray(reduce_with("int8")(jnp.asarray(xs)))
# phase 1: half-step of each source's per-tensor scale; phase 2: half-step
# of the reduced chunk's scale (bounded by the global max of the phase-1
# sums, overestimated slightly by max|exact| + phase-1 slack)
scales = np.abs(xs).max(axis=1) / 127.0
bound_q8 = 0.5 * scales.sum() + 0.5 * (np.abs(exact).max() / 127.0
                                       + scales.sum() / 127.0)
err_q8 = np.abs(q8 - exact)
assert err_q8.max() <= bound_q8 * 1.05 + 1e-6, (err_q8.max(), bound_q8)
print(f"psum err: bf16 {err_bf.max():.3e}  int8 {err_q8.max():.3e}")

# ---- wire dtypes and ring-wire ratio straight from compiled HLO ----------- #
hlos = {}
for method in ("none", "bf16", "int8"):
    def body(xs, m=method):
        x = xs[0]
        if m == "none":
            return jax.lax.psum(x, "data")
        if m == "bf16":
            return gcomp.compressed_psum_bf16(x, "data")
        return gcomp.compressed_psum_int8(x, "data")
    f = jax.jit(shd.shard_map(body, mesh8, (P("data"),), P()))
    hlos[method] = f.lower(
        jax.ShapeDtypeStruct((DP, N), jnp.float32)).compile().as_text()

wire = {m: ra.wire_bytes_by_dtype(t) for m, t in hlos.items()}
assert wire["none"].get("f32", 0) > 0, wire["none"]
assert wire["bf16"].get("u16", 0) > 0, wire["bf16"]
assert wire["int8"].get("s8", 0) > 0, wire["int8"]
tot = {m: sum(w.values()) for m, w in wire.items()}
assert tot["bf16"] <= 0.55 * tot["none"], (tot["bf16"], tot["none"])
assert tot["int8"] <= 0.35 * tot["none"], (tot["int8"], tot["none"])
print(f"wire bytes: {tot}")

# ---- error feedback carries across steps (sum of emitted ≈ sum fed) ------- #
g_const = {"w": jnp.asarray(rng.normal(size=(DP, 64)).astype(np.float32))}


def ef_step(g_stacked, ef_stacked):
    def body(g, ef):
        red, new_ef = gcomp.ef_compress_tree(
            {"w": g["w"][0]}, gcomp.ErrorFeedback({"w": ef["w"][0]}),
            "data", "int8")
        return red, {"w": new_ef.residual["w"][None]}
    return jax.jit(shd.shard_map(
        body, mesh8, (P("data"), P("data")), (P(), P("data"))))(
            g_stacked, ef_stacked)


ef = {"w": jnp.zeros((DP, 64), jnp.float32)}
emitted = np.zeros(64, np.float64)
for _ in range(20):
    red, ef = ef_step(g_const, ef)
    emitted += np.asarray(red["w"], np.float64)
target = np.asarray(g_const["w"], np.float64).mean(axis=0) * 20
drift = np.abs(emitted - target).max()
res = np.abs(np.asarray(ef["w"])).max()
# EF keeps the long-run mean unbiased: total drift stays bounded by the
# (per-step-scale) residual, instead of growing ~linearly with steps
assert drift <= 2.0 * np.abs(np.asarray(g_const["w"])).max() / 127.0 * DP, \
    drift
assert res > 0, "int8 EF residual should be nonzero"
print(f"EF drift over 20 steps {drift:.3e}, residual max {res:.3e}")

# ---- build_train_step: compressed trajectories track the exact one -------- #
cfg = get_reduced("qwen1.5-0.5b").replace(dtype="float32", num_layers=2,
                                          vocab_size=64, d_ff=96)
GB, S = 8, 16
shape = ShapeConfig("t", "train", S, GB)
model = build_model(cfg, impl="ref")


def make_batch(seed):
    r = np.random.default_rng(seed)
    return {
        "tokens": jnp.asarray(r.integers(0, cfg.vocab_size, (GB, S)),
                              jnp.int32),
        "labels": jnp.asarray(r.integers(0, cfg.vocab_size, (GB, S)),
                              jnp.int32),
        "loss_mask": jnp.ones((GB, S), jnp.float32)}


def run_steps(method, n_steps=4):
    par = ParallelConfig(dp=DP, mbs=1, zero_opt=False,
                         grad_compress=method)
    mesh = shd.section_mesh(jax.devices(), par)
    step, sh = step_mod.build_train_step(
        model, mesh, par, shape, opt_cfg=adamw.AdamWConfig(eps=1e-3))
    params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                            sh["params"])
    opt = jax.device_put(adamw.init(params), sh["opt"])
    ef = sh["ef_init"](params) if method != "none" else None
    losses = []
    for i in range(n_steps):
        args = (params, opt, make_batch(i), jnp.int32(i))
        if method == "none":
            params, opt, m = step(*args)
        else:
            params, opt, m, ef = step(*args, ef)
        losses.append(float(m["loss"]))
    if method == "int8":
        mx = max(float(jnp.max(jnp.abs(l)))
                 for l in jax.tree_util.tree_leaves(ef))
        assert mx > 0, "step-path int8 EF residual should be nonzero"
    return losses


base = run_steps("none")
for method, tol in (("bf16", 1e-3), ("int8", 5e-2)):
    ls = run_steps(method)
    dev = max(abs(a - b) / max(abs(b), 1e-8) for a, b in zip(ls, base))
    print(f"step losses {method}: max rel dev {dev:.2e}")
    assert dev < tol, (method, dev, ls, base)

# ---- CompoundRuntime: per-section knob, partial-grad (sum) semantics ------ #
B, S2, MBS, D = 8, 8, 4, 16
h_port = wl.Port("h", (S2, D), "float32")


def enc_fn(p, x):
    return {"h": jnp.tanh(x["x"] @ p["w"])}


def head_fn(p, x):
    pred = x["enc.h"] @ p["v"]
    return jnp.mean(jnp.square(pred - x["y"]))


def make_spec(method):
    par = ParallelConfig(dp=4, grad_compress=method)
    enc = wl.SectionSpec(
        "enc", cfg, par, enc_fn,
        {"w": cm.ParamSpec((D, D), (None, None), "normal", jnp.float32)},
        inputs={"x": wl.Field((S2, D), "float32")},
        emits=(h_port,))
    head = wl.SectionSpec(
        "head", cfg, par, head_fn,
        {"v": cm.ParamSpec((D, D), (None, None), "normal", jnp.float32)},
        inputs={"y": wl.Field((S2, D), "float32")},
        consumes=(wl.Consume("enc", h_port),),
        loss=True, critical=True)
    return wl.WorkloadSpec("t", (enc, head), seq_len=S2,
                           global_batch=B, mbs=MBS)


batches = [{"x": rng.normal(size=(B, S2, D)).astype(np.float32),
            "y": rng.normal(size=(B, S2, D)).astype(np.float32)}
           for _ in range(4)]

results = {}
for method in ("none", "bf16", "int8"):
    rt = wl.CompoundRuntime(make_spec(method),
                            opt_cfg=adamw.AdamWConfig(clip_norm=1.0))
    params, opts = rt.init(jax.random.PRNGKey(0))
    losses = []
    for i, b in enumerate(batches):
        params, opts, m = rt.train_iteration(params, opts, b, i)
        losses.append(float(m["loss"]))
    results[method] = losses
    if method == "int8":
        mx = max(float(jnp.max(jnp.abs(l)))
                 for l in jax.tree_util.tree_leaves(rt._ef))
        assert mx > 0, "runtime int8 EF residual should be nonzero"
    rt.shutdown()

base = results["none"]
for method, tol in (("bf16", 1e-3), ("int8", 5e-2)):
    ls = results[method]
    dev = max(abs(a - b) / max(abs(b), 1e-8) for a, b in zip(ls, base))
    print(f"runtime losses {method}: max rel dev {dev:.2e}")
    assert dev < tol, (method, dev, ls, base)

# ---- donated-state guard on the runtime install path ---------------------- #
rt = wl.CompoundRuntime(make_spec("none"),
                        opt_cfg=adamw.AdamWConfig(clip_norm=1.0))
params, opts = rt.init(jax.random.PRNGKey(0))
params2, opts2, _ = rt.train_iteration(params, opts, batches[0], 0)
for leaf in jax.tree_util.tree_leaves(opts):
    if hasattr(leaf, "delete") and not leaf.is_deleted():
        leaf.delete()
try:
    rt.install(params2, opts)
except adamw.DonatedStateError as e:
    assert "re-`place`" in str(e) or "place" in str(e).lower(), e
else:
    raise AssertionError("install() accepted a donated optimizer state")
rt.shutdown()

print("DRIVER_OK compression")
