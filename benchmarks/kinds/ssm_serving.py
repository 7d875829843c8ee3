"""Configuration kind `ssm-serving`: the session app's `/recommend-next`
through ServingLayer over HTTP with a hybrid state-space / attention decoder
(`jamba`: Mamba-1 layers, an attention layer every few, a dense feed-forward
in each) that generates a next basket token by token; one process holding
the chip, load from a generator process (benchmarks/seqgen.py).

The model is synthetic, from --seed: the layers' tensors made on the device
(`ops/jamba.py init_tensors`: normal x 0.02 and Mamba's published
initialisation of the recurrence), the item catalog drawn on the host at
bfloat16's values and used as BOTH the served view and the input embedding
(the embedding is tied), adopted as an artifact's tensors would be. The
server is the program as it ships: default reference.conf plus what a
read-only server on mem:// brokers with port 0 needs.

Also here, because later PRs may not change them: the kind's own copy of the
plain float32 reference in layer-sized pieces (`ref_*`), the comparison that
decides `correct` (`compare`, `summarise`) with its limits, and the functions
that compute the operations and bytes of a dispatch and of its scan
(`step_work`, `step_bytes`, `scan_work`).
"""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from pathlib import Path

import numpy as np

from benchmarks import latency, seqgen, seqtrace, timeline, xplane
from benchmarks.kinds.als_serving import _get, _sleep_until, queued_ahead_share, scrape
from benchmarks.kinds.seq_serving import draw_catalog, holds, ref_logits

# What `correct` holds the served answers to. For a sample of the window's
# own requests the reference runs ONE full forward pass over [session + the
# basket the system chose] and its hidden rows at the four positions, scored
# over the catalog, are held against what the timed path returned. A served
# score is the float32 dot, on the host, of the position's hidden state with
# the item's row, so it differs from the reference's logit by the hidden
# state's error alone. Distances are in units of the position's largest
# |logit| over the catalog; a position's `score_err` is the root mean square
# over its candidates. Nothing here is discontinuous (no router): a fault in
# the arithmetic moves every request, so the tight limits are held by the
# quartile over the sampled requests at the worst basket position, as kind
# seq-serving holds its own, and one loose limit by the worst reading.
#
# The float32 reference lies a rounding away from a bfloat16 program (2^-9 at
# every product), and what a fault adds can hide inside that. So the reference
# is computed a second time WITH the configuration's stated rounding (every
# product's inputs at bfloat16's values, compiled without XLA's excess
# precision) and the served scores are held to THAT too: `stated_err`.
#
# The limits, each above every sound reading on the chip and below the reading
# of the control it is there to catch (my chip runs, PR 37; PERF.md has every
# reading). Sound, over twenty-seven seeds: `stated_err_quartile` 1.44e-3 to
# 2.38e-3, `score_err_quartile` 4.67e-3 to 5.49e-3. The controls, one process,
# twice: the recurrence's state kept in bfloat16 reads `stated_err_quartile`
# 4.33e-3 and 4.53e-3 and moves nothing else (against the float32 reference it
# hides inside the rounding: 4.77e-3 beside the sound 4.89e-3); the conv's tail
# not carried reads 0.79-0.81 on both quartiles and padding that advances the
# state 0.57-0.67, overlap 0.
CHECK_REQUESTS = 32
REFERENCE_BATCH = 16   # sessions a reference dispatch
# against the float32 reference, the quartile: float32 leaves the order of
# accumulation alone (5e-8 on the CPU; a bfloat16 state reads 3e-6 there); for
# bfloat16 it is the net under a fault the stated-rounding reference would
# share, four times the sound program's rounding
SCORE_TIGHT = {"float32": 1.0e-6, "bfloat16": 2.0e-2}
# against the reference with the stated rounding, the same quartile. What is
# left is not the order of accumulation alone: where the served path and the
# reference differ by 1e-6 before a rounding to bfloat16 they round apart, and
# 28 layers of such decisions read 1.4-2.4e-3 on the chip (4e-8 on the CPU,
# whose products are the reference's own). Between that and the bfloat16
# state's 4.33e-3: a third of the way up from each side
STATED_TIGHT = 3.2e-3
SCORE_LOOSE = 5.0e-2   # the worst position of all, the item fed back and the last candidate: sound at most 2.3e-2, the controls over 1
MIN_OVERLAP = 8        # of 10 candidates the reference's, by the same quartile (sound 9: the tenth logit lies 1e-2 from the eleventh)
MIN_OVERLAP_WORST = 4  # and in the worst position of all (sound 6-8 over 27 runs; the controls 0)
WARM_MIN_S = 5.0
WARM_CYCLES = 5
TRACE_MAX_S = 12.0
# an op counts under the first scope its op_name holds: the scan lies inside
# the mixer's scope
SCOPES = ("jamba.scan", "jamba.mamba", "jamba.attn", "jamba.mlp", "jamba.head")
PROGRAMS = {"prefill": "jit_prefill", "decode": "jit_decode_step"}


# -- the algorithm's operations and bytes ------------------------------------------

def _sizes(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    attn = sum(1 for l in range(layers) if l % cfg["attn_layer_period"] == cfg["attn_layer_offset"])
    return {
        "h": h, "f": cfg["intermediate_size"], "c": cfg["mamba_expand"] * h, "n": cfg["mamba_d_state"],
        "k": cfg["mamba_d_conv"], "r": cfg["mamba_dt_rank"], "d": h // cfg["num_attention_heads"],
        "q": h, "kv": cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"]),
        "attn": attn, "mamba": layers - attn, "v": cfg["vocab_size"],
    }


def scan_work(tokens: float, rows: float, cfg: dict) -> tuple[float, float]:
    """(FLOPs, bytes) the conv and the recurrence of ONE Mamba layer need for
    `tokens` real positions in `rows` sequences: a position's conv (2 K C),
    dt A and its exp (2 N C), (dt x) B (C + N C), the state's update (2 N C)
    and y = h C + D x (2 N C + 2 C); x read twice (conv, scan), dt and z-free
    y, B and C in float32, and each sequence's state and conv inputs read and
    written once. Padding positions and rows are the implementation's."""
    s = _sizes(cfg)
    c, n, k = s["c"], s["n"], s["k"]
    flops = tokens * (2.0 * k * c + 7.0 * n * c + 3.0 * c)
    moved = tokens * 4.0 * (5.0 * c + 2.0 * n) + rows * 2.0 * 4.0 * (n + k - 1) * c
    return flops, moved


def step_work(tokens: float, context: float, head_tokens: float, cfg: dict) -> float:
    """FLOPs the MODEL needs for one dispatch of `tokens` real tokens, an
    attention layer's attending over `context` positions on average,
    `head_tokens` of them also taking logits over the catalog: the Mamba
    layers' four projections, conv and recurrence, the attention layers'
    projections, scores and values, every layer's feed-forward, the head."""
    s = _sizes(cfg)
    h, c, n, r = s["h"], s["c"], s["n"], s["r"]
    mixer = 2.0 * h * 2 * c + 2.0 * c * (r + 2 * n) + 2.0 * r * c + 2.0 * c * h
    mixer += scan_work(1.0, 0.0, cfg)[0]
    attend = 2.0 * h * (s["q"] + 2 * s["kv"]) + 2.0 * s["q"] * h + 2.0 * 2.0 * s["q"] * context
    mlp = 3 * 2.0 * h * s["f"]
    layers = s["mamba"] * (mixer + mlp) + s["attn"] * (attend + mlp)
    return tokens * layers + head_tokens * 2.0 * h * s["v"]


def weight_bytes(cfg: dict, itemsize: int = 2) -> float:
    """Bytes of every layer's weights (the embedding apart: a dispatch
    gathers a few of its rows)."""
    s = _sizes(cfg)
    h, c, n, r, f = s["h"], s["c"], s["n"], s["r"], s["f"]
    mlp = 3.0 * h * f
    mamba = h * 2 * c + s["k"] * c + c + c * (r + 2 * n) + r * c + c * h
    attn = h * (s["q"] + 2 * s["kv"]) + s["q"] * h
    stored = s["mamba"] * (mamba + mlp) + s["attn"] * (attn + mlp)
    return stored * itemsize + s["mamba"] * (n * c + 2 * c) * 4.0


def step_bytes(tokens: float, rows: float, head: bool, cfg: dict, itemsize: int = 2) -> float:
    """Bytes one dispatch has to move: every weight once, the state of its
    `rows` real sequences (the recurrent state and conv inputs written by a
    prefill, read and written by a step; keys and values a row a token) and,
    for a step, the head's view of the catalog once."""
    s = _sizes(cfg)
    recurrent = s["mamba"] * (s["n"] + s["k"] - 1) * s["c"] * 4.0
    kv = s["attn"] * 2.0 * s["kv"] * itemsize
    moved = weight_bytes(cfg, itemsize) + rows * recurrent * (2.0 if head else 1.0) + tokens * kv
    if head:
        moved += s["v"] * s["h"] * itemsize
    return moved


# -- the plain reference, a layer at a time: float32, `highest`, no cache -------------

def _as(x, act):
    """x at the values dtype `act` holds, still float32 (None: as it is)."""
    import jax.numpy as jnp

    return x if act is None else x.astype(act).astype(jnp.float32)


def _norm(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w.astype(jnp.float32)


def ref_mlp(cfg: dict, p: dict, x, act=None):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    u = _as(_norm(x, p["ln2"], cfg["rms_norm_eps"]), act)
    mid = _as(jax.nn.silu(u @ p["wg"].astype(f32)) * (u @ p["wu"].astype(f32)), act)
    return x + mid @ p["wd"].astype(f32)


def ref_mamba_layer(cfg: dict, p: dict, x, act=None):
    """x [B,T,H] float32 -> the layer's output: the mixer with the recurrence
    one position after another from a zero state, then the feed-forward. With
    `act` the inputs of every product are at that dtype's values; the conv,
    dt, the recurrence and its state stay float32, as the configuration says."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    s = _sizes(cfg)
    c, n, r, k, eps = s["c"], s["n"], s["r"], s["k"], cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        u = _as(_norm(x, p["ln1"], eps), act)
        xz = u @ p["in_proj"].astype(f32)
        xs, z = xz[..., :c], xz[..., c:]
        t = xs.shape[1]
        window = jnp.pad(xs, ((0, 0), (k - 1, 0), (0, 0)))
        w = p["conv_w"].astype(f32)
        xc = jax.nn.silu(sum(window[:, j:j + t] * w[j] for j in range(k)) + p["conv_b"].astype(f32))
        dbc = _as(xc, act) @ p["x_proj"].astype(f32)
        dt = _as(_norm(dbc[..., :r], p["dt_norm"], eps), act)
        b = _norm(dbc[..., r:r + n], p["b_norm"], eps)
        cm = _norm(dbc[..., r + n:], p["c_norm"], eps)
        dt = jax.nn.softplus(dt @ p["dt_proj"].astype(f32) + p["dt_bias"])
        a = -jnp.exp(p["A_log"])                                                  # [N,C]

        def one(h, xs_):
            x_t, dt_t, b_t, c_t = xs_                                             # [B,C] [B,C] [B,N] [B,N]
            h = jnp.exp(dt_t[:, None, :] * a[None]) * h + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
            return h, jnp.sum(h * c_t[:, :, None], axis=1) + p["D"] * x_t

        h0 = jnp.zeros((x.shape[0], n, c), f32)
        _, y = jax.lax.scan(one, h0, tuple(jnp.swapaxes(v, 0, 1) for v in (xc, dt, b, cm)))
        y = jnp.swapaxes(y, 0, 1)
        x = x + _as(y * jax.nn.silu(z), act) @ p["out_proj"].astype(f32)
        return ref_mlp(cfg, p, x, act)


def ref_attention_layer(cfg: dict, p: dict, x, act=None):
    """x [B,T,H] float32 -> the layer's output: full causal attention over
    the sequence (one key-value head serves every query head; no positions),
    then the feed-forward."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    s = _sizes(cfg)
    heads, d = cfg["num_attention_heads"], s["d"]
    kv_heads = cfg["num_key_value_heads"]
    with jax.default_matmul_precision("highest"):
        bsz, t = x.shape[0], x.shape[1]
        u = _as(_norm(x, p["ln1"], cfg["rms_norm_eps"]), act)
        q = _as((u @ p["wq"].astype(f32)).reshape(bsz, t, heads, d), act)
        k = _as((u @ p["wk"].astype(f32)).reshape(bsz, t, kv_heads, d), act)
        v = _as((u @ p["wv"].astype(f32)).reshape(bsz, t, kv_heads, d), act)
        k = jnp.repeat(k, heads // kv_heads, axis=2)
        v = jnp.repeat(v, heads // kv_heads, axis=2)
        sc = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(d)
        sc = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], sc, -jnp.inf)
        prob = _as(jax.nn.softmax(sc, axis=-1), act)
        o = jnp.einsum("bhts,bshd->bthd", prob, v).reshape(bsz, t, heads * d)
        x = x + _as(o, act) @ p["wo"].astype(f32)
        return ref_mlp(cfg, p, x, act)


def ref_hidden(config: dict, params: dict, tokens: np.ndarray, act=None, compiled: dict | None = None):
    """tokens [B,T] int32 -> final-normed hidden [B,T,H] float32 by the plain
    form, ONE layer's program at a time over the model's own tensors (a
    layer's float32 copy lives only inside its call): the model is never held
    twice. Compiled without XLA's excess precision, so a stated rounding is
    computed as stated. `compiled` keeps the two layer programs between calls
    of one shape and one `act`."""
    import jax
    import jax.numpy as jnp

    x = params["E_in"][jnp.asarray(tokens)].astype(jnp.float32)
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    compiled = {} if compiled is None else compiled
    for p in params["layers"]:
        kind = ref_attention_layer if "wq" in p else ref_mamba_layer
        if kind not in compiled:
            compiled[kind] = jax.jit(partial(kind, config, act=act)).lower(
                jax.tree.map(shape, p), shape(x)
            ).compile(compiler_options={"xla_allow_excess_precision": False})
        x = compiled[kind](p, x)
    return _norm(x, params["final_norm"], config["rms_norm_eps"])


# -- the comparison that decides `correct` --------------------------------------------

def basket_tokens(config: dict, answer: list, session: np.ndarray) -> np.ndarray | None:
    """[session + the items the served basket holds but the last]: the tokens
    whose full forward pass gives, at its last `basket` positions, the hidden
    states the served path ranked; None where the answer's form is wrong."""
    basket = config["basket"]
    try:
        fixed = [int(e["item"][1:]) for e in answer]
        at = [int(e["step"]) for e in answer]
    except (ValueError, TypeError, KeyError, IndexError):
        return None
    if len(fixed) != basket or at != list(range(basket)):
        return None
    return np.concatenate([session, fixed[:-1]]).astype(np.int32)


def compare(config: dict, answer: list, session: np.ndarray, logits, how_many: int, rounded=None) -> list[dict]:
    """One served answer against the reference's logits [basket, items] at
    its positions (`rounded`: the same with the stated rounding). One dict a
    basket position: {"fault", "score_err", "rounding", "stated_err",
    "fixed_gap", "overlap", "candidate_gap"}, the distances in units of the
    position's largest |logit|; numbers None where the form is wrong."""
    keys = ("fault", "score_err", "rounding", "stated_err", "fixed_gap", "overlap", "candidate_gap")
    out = [dict.fromkeys(keys) for _ in range(config["basket"])]
    try:
        fixed = [int(e["item"][1:]) for e in answer]
        pages = [[(int(i[1:]), float(s)) for i, s in e["next"]] for e in answer]
        formed = logits is not None and basket_tokens(config, answer, session) is not None
    except (ValueError, TypeError, KeyError, IndexError):
        formed = False
    if not formed:
        for o in out:
            o["fault"] = "not one entry a basket position with the steps 0..B-1"
        return out
    for b, o in enumerate(out):
        row = logits[b]
        scale = float(np.max(np.abs(row)))
        rows = [r for r, _ in pages[b]]
        got = np.asarray([s for _, s in pages[b]], dtype=np.float64)
        if len(rows) != how_many:
            o["fault"] = f"{len(rows)} candidates served, not {how_many}"
        elif set(rows) & set(session.tolist()):
            o["fault"] = "an item of the session was served"
        elif np.any(np.diff(got) > 0):
            o["fault"] = "scores not descending"
        else:
            o["score_err"] = float(np.sqrt(np.mean((got - row[rows]) ** 2))) / scale
            if rounded is not None:
                low = rounded[b]
                o["rounding"] = float(np.sqrt(np.mean((low[rows] - row[rows]) ** 2))) / scale
                o["stated_err"] = float(np.sqrt(np.mean((got - low[rows]) ** 2))) / scale
            # the item fed back is the head's argmax: how far under the reference's best
            o["fixed_gap"] = float(np.max(row) - row[fixed[b]]) / scale
            open_ = row.copy()
            open_[session] = -np.inf
            ref_top = np.argsort(-open_, kind="stable")[:how_many]
            o["overlap"] = len(set(rows) & set(ref_top.tolist()))
            o["candidate_gap"] = float(max(0.0, open_[ref_top[-1]] - min(open_[r] for r in rows))) / scale
    return out


def summarise(per_request: list[list[dict]], dtype: str = "bfloat16") -> dict:
    """The compared numbers of `compare`'s readings over the sampled
    requests: by basket position the quartile over the requests (the worst
    position's is reported), and the worst reading of all."""
    flat = [o for req in per_request for o in req]
    basket = len(per_request[0]) if per_request else 0

    def by_position(key, q, pick):
        read = []
        for b in range(basket):
            values = [req[b][key] for req in per_request if req[b][key] is not None]
            if values:
                read.append(float(np.percentile(values, q)))
        return pick(read) if read else None

    def worst(key, pick):
        values = [o[key] for o in flat if o[key] is not None]
        return pick(values) if values else None

    out = {"malformed_answers": [sum(1 for o in flat if o["fault"]), "==", 0]}
    if worst("stated_err", max) is not None:  # a configuration that states a rounding
        out["stated_err_quartile"] = [by_position("stated_err", 25, max), "<=", STATED_TIGHT]
    out.update(
        score_err_quartile=[by_position("score_err", 25, max), "<=", SCORE_TIGHT[dtype]],
        score_err_worst=[worst("score_err", max), "<=", SCORE_LOOSE],
        fixed_gap_worst=[worst("fixed_gap", max), "<=", SCORE_LOOSE],
        candidate_gap_worst=[worst("candidate_gap", max), "<=", SCORE_LOOSE],
        overlap_quartile=[by_position("overlap", 25, min), ">=", MIN_OVERLAP],
        overlap_worst=[worst("overlap", min), ">=", MIN_OVERLAP_WORST],
    )
    return out


# -- the model from the seed ----------------------------------------------------------------

def extensions(config: dict) -> dict:
    """The artifact's extensions: the source's own keys, as strings."""
    keys = (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "intermediate_size",
        "num_hidden_layers", "vocab_size", "attn_layer_period", "attn_layer_offset", "mamba_d_state",
        "mamba_d_conv", "mamba_dt_rank", "mamba_expand", "rms_norm_eps", "basket", "max_len", "dtype",
    )
    return dict({k: str(config[k]) for k in keys}, encoder="jamba")


def build(cell: dict, seed: int, info):
    """The model from the seed and the server around it, started:
    (serving, manager, state, e_host). The caller closes `serving`."""
    # a tree without the decoder fails here, at once, before any set-up
    from oryx_tpu.ops import jamba

    import jax
    import jax.numpy as jnp

    from oryx_tpu.apps.seq.serving import SeqServingModel, SeqServingModelManager
    from oryx_tpu.apps.seq.state import adopt_model
    from oryx_tpu.bus.broker import topics
    from oryx_tpu.common.config import load_config
    from oryx_tpu.serving.server import ServingLayer

    config = cell["config"]
    n_items = config["vocab_size"]  # every id is an item
    t_build = time.monotonic()
    ext = extensions(config)
    enc = jamba.JambaEncoder.from_extensions(ext.get)
    tensors = jamba.init_tensors(enc.cfg, seed, enc.dtype)
    # the tied embedding: the catalog's rows are the input embedding's
    e_host = draw_catalog(seed, n_items, config["hidden_size"])
    tensors["E_in"] = jnp.asarray(e_host, dtype=enc.dtype)
    tensors["E"] = e_host
    state = adopt_model(None, ext.get, tensors, [f"i{j}" for j in range(n_items)])
    jax.block_until_ready(state.params)
    info(phase="model_built", seconds=time.monotonic() - t_build, parameters=jamba.param_count(enc.cfg))

    broker = "mem://bench"
    overlay = {
        "oryx.id": "bench",
        "oryx.input-topic.broker": broker,
        "oryx.update-topic.broker": broker,
        "oryx.serving.api.port": 0,
        "oryx.serving.api.read-only": True,
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common",
            "oryx_tpu.serving.resources.seq",
        ],
        "oryx.monitoring.flight.dir": str(Path(cell["scratch"]) / "flight"),
    }
    if jax.devices()[0].platform == "tpu":
        overlay["oryx.compute.platform"] = "tpu"
    cfg = load_config(overlay=overlay)
    topics.maybe_create(broker, "OryxUpdate", partitions=1)
    manager = SeqServingModelManager(cfg)
    manager.model = SeqServingModel(state, sync=manager.sync)
    serving = ServingLayer(cfg, model_manager=manager)
    serving.start()
    return serving, manager, state, e_host


def reference_logits(config: dict, params: dict, e_dev, asked: list[np.ndarray], act) -> list[np.ndarray]:
    """The reference's logits [basket, items] at the last `basket` positions
    of every token array in `asked`, REFERENCE_BATCH forward passes a
    dispatch (right-padded: the pass is causal, so padding changes nothing
    before it)."""
    basket, width = config["basket"], config["max_len"] + config["basket"]
    out, compiled = [], {}
    for lo in range(0, len(asked), REFERENCE_BATCH):
        group = asked[lo:lo + REFERENCE_BATCH]
        padded = np.zeros((REFERENCE_BATCH, width), dtype=np.int32)
        for j, tokens in enumerate(group):
            padded[j, : len(tokens)] = tokens
        z = ref_hidden(config, params, padded, act, compiled)
        rows = np.stack([np.arange(len(t) - basket, len(t)) for t in group])
        zb = z[np.arange(len(group))[:, None], rows]                              # [G, basket, H]
        logits = ref_logits(zb.reshape(len(group) * basket, -1), e_dev)
        out += [logits[j * basket:(j + 1) * basket] for j in range(len(group))]
    return out


def check(base: str, config: dict, traffic: dict, state, e_host, sessions: list, sample: list[int], info):
    """The sampled sessions asked again, together, and each answer against
    the reference's one full pass over it: (readings of `compare`, faults)."""
    import jax.numpy as jnp

    with ThreadPoolExecutor(len(sample)) as pool:
        answers = list(pool.map(
            lambda i: _get(f"{base}{seqgen.session_path(traffic, sessions[i])}"), sample
        ))
    faults, served = [], []
    for i, (status, body) in zip(sample, answers):
        if status != 200:
            faults.append(f"request {i}: status {status}")
        else:
            served.append((json.loads(body), sessions[i]))
    t_ref = time.monotonic()
    asked = [basket_tokens(config, answer, session) for answer, session in served]
    sound = [j for j, t in enumerate(asked) if t is not None]
    e_dev = jnp.asarray(e_host, dtype=jnp.bfloat16)  # bf16 holds the catalog's values exactly
    tokens = [asked[j] for j in sound]
    exact = dict(zip(sound, reference_logits(config, state.params, e_dev, tokens, None)))
    # the configuration's stated rounding, where it states one below float32
    act = None if config["dtype"] == "float32" else jnp.dtype(config["dtype"])
    rounded = (
        dict(zip(sound, reference_logits(config, state.params, e_dev, tokens, act))) if act is not None else {}
    )
    readings = [
        compare(config, answer, session, exact.get(j), int(traffic["how_many"]), rounded.get(j))
        for j, (answer, session) in enumerate(served)
    ]
    keys = ("score_err", "rounding", "stated_err", "fixed_gap", "overlap", "candidate_gap")
    info(phase="reference", seconds=time.monotonic() - t_ref, forwards=len(tokens), readings=[
        [[None if o[k] is None else round(o[k], 6) for k in keys] for o in req] for req in readings
    ])
    for req in readings:
        faults += [f"a basket position: {o['fault']}" for o in req if o["fault"]]
    return readings, faults


def run(cell: dict, seed: int, seconds: float, trace: bool, t_process: float, info) -> dict:
    """One run of one cell. `cell` = {config, traffic, chips, scratch}."""
    import jax

    from oryx_tpu.common.perfstats import get_perfstats

    config, traffic = cell["config"], cell["traffic"]
    n_items = config["vocab_size"]
    # the generator's names for the basket and its steps (benchmarks/seqgen.py)
    for key in ("block_length", "denoise_steps"):
        if traffic[key] != config["basket"]:
            raise ValueError(f"traffic's {key} and the configuration's basket disagree")
    serving, manager, state, e_host = build(cell, seed, info)

    # the cyclic collector stops every thread of the server while it runs:
    # time each collection (gc_pause_share)
    collections: list[tuple[float, float]] = []  # (monotonic start, seconds)

    def on_gc(phase: str, _info: dict) -> None:
        now = time.monotonic()
        if phase == "start":
            collections.append((now, 0.0))
        else:
            collections[-1] = (collections[-1][0], now - collections[-1][0])

    gc.callbacks.append(on_gc)
    base = f"http://127.0.0.1:{serving.port}"
    gen = None
    try:
        started = scrape(base)  # before this run's first request
        # -- warm-up, part 1: one request uploads the view and compiles (or
        # loads) every shape of the decoder and the scan's; a second, alone,
        # times one request
        t_prime = time.monotonic()
        probe = seqgen.draw_sessions(seed + 1, n_items, traffic, 2)
        for attempt, session in zip(("first", "cycle"), probe):
            t_req = time.monotonic()
            status, body = _get(f"{base}{seqgen.session_path(traffic, session)}")
            if status != 200:
                raise RuntimeError(f"priming request -> {status}: {body[:200]!r}")
            cycle_s = time.monotonic() - t_req
            info(phase=f"prime_{attempt}", seconds=cycle_s)
        warm_s = float(math.ceil(max(WARM_MIN_S, WARM_CYCLES * cycle_s)))
        spec = {
            "port": serving.port, "seed": seed, "traffic": traffic, "items": n_items,
            "seconds": seconds, "warm_s": warm_s,
        }
        gen = subprocess.Popen(
            [sys.executable, seqgen.__file__, json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env={k: v for k, v in os.environ.items() if k not in ("PYTHONPATH", "JAX_PLATFORMS")},
        )
        if gen.stdout.readline().strip() != "READY":
            raise RuntimeError("the load generator did not start")

        # -- warm-up, part 2: the cell's own traffic, then the window
        t0 = time.monotonic() + 0.25
        gen.stdin.write(json.dumps({"t0": t0}) + "\n")
        gen.stdin.flush()
        t_open, t_close = t0 + warm_s, t0 + warm_s + seconds
        _sleep_until(t_open)
        setup_s = time.time() - t_process
        before = scrape(base)
        trace_out = timeline_out = found = None
        if trace:
            trace_dir = Path(cell["scratch"]) / "trace"
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            _sleep_until(t_open + 0.25)
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
            _sleep_until(min(time.monotonic() + TRACE_MAX_S, t_close - 0.5))
            jax.profiler.stop_trace()
            found = xplane.find_xplane(trace_dir)
            if found:
                trace_out = xplane.reduce_trace(found, prefer=timeline.REGION_PREFIX)
                timeline_out = timeline.parse(found)
        _sleep_until(t_close)
        after = scrape(base)
        ring = get_perfstats().records_since(t_open - 1.0)
        records = [r for r in ring if t_open <= r.t_start < t_close]
        pauses = [s for t, s in collections if t_open <= t < t_close]
        out, _ = gen.communicate(timeout=seconds + 240)
        result = json.loads(out.strip().splitlines()[-1])
        gen = None

        # -- correctness, outside the timing: sampled requests of the window,
        # asked again together, against the reference's pass over each
        good, attempted, failed = latency.window_latencies(result)
        n_requests = len(result["due"])
        sessions = seqgen.draw_sessions(seed, n_items, traffic, n_requests)
        in_window = np.flatnonzero(np.asarray(result["in_window"], dtype=bool))
        rng = np.random.default_rng([int(seed), 3])
        sample = rng.choice(in_window, size=min(CHECK_REQUESTS, len(in_window)), replace=False)
        readings, faults = check(base, config, traffic, state, e_host, sessions, sample.tolist(), info)
        final = scrape(base)
        wrong_bodies = sum(
            n for kind, n in result["errors"].items()
            if kind in ("unparsable", "wrong_block", "wrong_count", "known_item")
        )
        delta = {s: after[s] - before.get(s, 0.0) for s in after}
        compiles = sum(v for s, v in delta.items() if s.startswith("oryx_xla_compiles_total"))
        # every event of every session sent (the probes, the generator's, the
        # sample asked again) but its last has to have run through a prefill
        sent = list(probe) + sessions + [sessions[i] for i in sample.tolist()]
        whole = lambda series: final.get(series, 0.0) - started.get(series, 0.0)  # noqa: E731
        answered = whole("oryx_seq_blocks_total")
        prefilled = whole('oryx_seq_step_tokens_total{kind="prefill",tokens="real"}')
        timed_out = sum(n for kind, n in result["errors"].items() if kind == "timeout")
        compared = dict(
            {"requests_compared": [len(readings), "==", len(sample)]},
            **summarise(readings, config["dtype"]),
            wrong_bodies_in_window=[wrong_bodies, "==", 0],
            compiles_in_window=[compiles, "==", 0],
            # over the whole run, read when nothing is in flight
            steps_per_basket=[whole("oryx_seq_denoise_steps_total") / answered if answered else None,
                              "==", config["basket"]],
            dropped_events=[sum(len(s) - 1 for s in sent) - prefilled if not timed_out else None, "==", 0],
            host_fallbacks=[delta.get("oryx_topk_host_fallbacks", 0.0), "==", 0],
            topk_shapes=[len({(r.padded_rows, r.k_bucket) for r in records}), "==", 1],
            dispatches_not_exact=[sum(1 for r in records if r.score_mode != "exact"), "==", 0],
            good_in_window=[len(good), ">=", 1],
        )
        faults += [f"{name} = {compared[name][0]} breaks its limit" for name in holds(compared)]
        for f in faults:
            print(f"ssm_serving: {f}", file=sys.stderr)

        steps_out = None
        if found:
            steps_out = seqtrace.split(seqtrace.parse(found), _compiled_texts(manager.model), SCOPES)
        late = [ms for ms, w in zip(result["late_ms"], result["in_window"]) if w and ms is not None]
        info(
            generator_processes=1, connections_opened=result["connections_opened"],
            errors=result["errors"], warm_s=warm_s,
            in_flight_at_window_end=latency.in_flight_at(result, warm_s + seconds),
            prime_s=t_open - t_prime,
            gen_late_p95_ms=latency.percentile(late, 95) if late else None,
            latency_p95_ms=latency.percentile(good, 95) if good else None,
            collector_pauses_s=[round(s, 4) for s in pauses if s > 0.05],
            dispatches=len(records),
            rows_per_dispatch=sum(r.rows for r in records) / len(records) if records else None,
            shapes=sorted({(r.padded_rows, r.k_bucket) for r in records}),
            queued_ahead_share=queued_ahead_share(records),
            encoder_steps=sum(delta.get(f'oryx_seq_steps_total{{kind="{kind}"}}', 0.0) for kind in PROGRAMS),
            prefill_tokens_per_step=_ratio(delta, "prefill"), decode_tokens_per_step=_ratio(delta, "decode"),
            slot_state_bytes={
                kind: final.get(f'oryx_seq_slot_state_bytes{{state="{kind}"}}') for kind in ("recurrent", "kv")
            },
        )
    finally:
        gc.callbacks.remove(on_gc)
        if gen is not None:
            gen.kill()
            gen.wait()
        serving.close()

    return {
        "correct": not faults and bool(good),
        "attempted": attempted,
        "failed": failed,
        "setup_s": setup_s,
        "end_to_end": {"p50_ms": latency.percentile(good, 50) if good else None},
        "sources": {
            "config": config,
            "traffic": traffic,
            "counters": delta,
            "dispatch_records": [
                {"rows": r.rows, "padded_rows": r.padded_rows, "k_bucket": r.k_bucket}
                for r in records
            ],
            "generator": {"late_ms": late, "latency_ms": good},
            "collector": {"window_s": seconds, "pauses_s": pauses},
            "trace": trace_out,
            "timeline": timeline_out,
            # the traced window's device time by decoder program and scope
            "steps": steps_out,
        },
        "compared": compared,
    }


def _ratio(delta: dict, kind: str) -> float | None:
    n = delta.get(f'oryx_seq_steps_total{{kind="{kind}"}}', 0.0)
    real = delta.get(f'oryx_seq_step_tokens_total{{kind="{kind}",tokens="real"}}', 0.0)
    return real / n if n else None


def _compiled_texts(model) -> dict[str, list[str]]:
    """The compiled text of every decoder program the engine runs, by the
    program's name on the device trace: lowered again from the live arrays'
    shapes (a persistent compile cache makes it a load)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.ops import jamba

    engine = model._engine()
    enc = engine.encoder
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    params, state = jax.tree.map(shape, engine.params), jax.tree.map(shape, engine.state)
    view = engine.head()[0]
    rows = lambda n, dt: jax.ShapeDtypeStruct((n,), dt)  # noqa: E731
    texts = {PROGRAMS["prefill"]: [], PROGRAMS["decode"]: []}
    for bucket in enc.length_buckets:
        p = rows(enc.prefill_rows, jnp.int32)
        lowered = jamba.prefill.lower(
            enc.cfg, params, state, jax.ShapeDtypeStruct((enc.prefill_rows, bucket), jnp.int32), p, p, p
        )
        texts[PROGRAMS["prefill"]].append(lowered.compile().as_text())
    d = enc.step_rows
    lowered = jamba.decode_step.lower(
        enc.cfg, params, state, shape(view), jax.ShapeDtypeStruct((), jnp.int32),
        rows(d, jnp.int32), rows(d, jnp.int32), rows(d, jnp.bool_), rows(d, jnp.int32),
    )
    texts[PROGRAMS["decode"]].append(lowered.compile().as_text())
    return texts
