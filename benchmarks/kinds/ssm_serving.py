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

import math
import time
from functools import partial

import numpy as np

from benchmarks.kinds import _encoder
from benchmarks.kinds._encoder import holds  # noqa: F401 - the kind's tests read it here
from benchmarks.kinds.seq_serving import draw_catalog, ref_logits

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
    requests, under this kind's limits."""
    return _encoder.summarise(
        per_request, SCORE_TIGHT[dtype], STATED_TIGHT, SCORE_LOOSE, MIN_OVERLAP, MIN_OVERLAP_WORST
    )


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

    from oryx_tpu.apps.seq.state import adopt_model

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
    return (*_encoder.serve(cell, state), state, e_host)


def reference_logits(hidden, config: dict, params: dict, e_dev, asked: list[np.ndarray], act) -> list[np.ndarray]:
    """The reference's logits [basket, items] at the last `basket` positions
    of every token array in `asked`, REFERENCE_BATCH forward passes a
    dispatch (right-padded: the pass is causal, so padding changes nothing
    before it). `hidden` is the kind's plain forward, `ref_hidden`'s form."""
    basket, width = config["basket"], config["max_len"] + config["basket"]
    out, compiled = [], {}
    for lo in range(0, len(asked), REFERENCE_BATCH):
        group = asked[lo:lo + REFERENCE_BATCH]
        padded = np.zeros((REFERENCE_BATCH, width), dtype=np.int32)
        for j, tokens in enumerate(group):
            padded[j, : len(tokens)] = tokens
        z = hidden(config, params, padded, act, compiled)
        rows = np.stack([np.arange(len(t) - basket, len(t)) for t in group])
        zb = z[np.arange(len(group))[:, None], rows]                              # [G, basket, H]
        logits = ref_logits(zb.reshape(len(group) * basket, -1), e_dev)
        out += [logits[j * basket:(j + 1) * basket] for j in range(len(group))]
    return out


def check_baskets(hidden, config: dict, traffic: dict, state, e_host, served: list) -> tuple[list, int]:
    """The sampled sessions' answers (`served`: [(answer, session)]), each
    against the reference's one full pass (`hidden`) over it: (readings of
    `compare`, the reference's forward passes). Kind joyai-serving's check
    too, over its own `ref_hidden`."""
    import jax.numpy as jnp

    asked = [basket_tokens(config, answer, session) for answer, session in served]
    sound = [j for j, t in enumerate(asked) if t is not None]
    e_dev = jnp.asarray(e_host, dtype=jnp.bfloat16)  # bf16 holds the catalog's values exactly
    tokens = [asked[j] for j in sound]
    exact = dict(zip(sound, reference_logits(hidden, config, state.params, e_dev, tokens, None)))
    # the configuration's stated rounding, where it states one below float32
    act = None if config["dtype"] == "float32" else jnp.dtype(config["dtype"])
    rounded = (
        dict(zip(sound, reference_logits(hidden, config, state.params, e_dev, tokens, act))) if act is not None else {}
    )
    readings = [
        compare(config, answer, session, exact.get(j), int(traffic["how_many"]), rounded.get(j))
        for j, (answer, session) in enumerate(served)
    ]
    return readings, len(tokens)


def basket_invariants(config: dict, final: dict, started: dict, sent: list, timed_out: int) -> dict:
    """The entries of `compared` every kind of autoregressive basket holds,
    over the whole run: every basket took its steps, and every event of every
    session sent but its last ran through a prefill."""
    whole = lambda series: final.get(series, 0.0) - started.get(series, 0.0)  # noqa: E731
    answered = whole("oryx_seq_blocks_total")
    prefilled = whole('oryx_seq_step_tokens_total{kind="prefill",tokens="real"}')
    return {
        "steps_per_basket": [whole("oryx_seq_denoise_steps_total") / answered if answered else None,
                             "==", config["basket"]],
        "dropped_events": [sum(len(s) - 1 for s in sent) - prefilled if not timed_out else None, "==", 0],
    }


def compiled_texts(model) -> dict[str, list[str]]:
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


KIND = _encoder.Kind(
    name="ssm_serving", programs=PROGRAMS, scopes=SCOPES, build=build, check=partial(check_baskets, ref_hidden),
    summarise=summarise, invariants=basket_invariants, compiled_texts=compiled_texts, position="basket",
    slot_states=("recurrent", "kv"),
)


def run(cell: dict, seed: int, seconds: float, trace: bool, t_process: float, info) -> dict:
    """One run of one cell. `cell` = {name, config, traffic, chips, scratch}."""
    return _encoder.run(KIND, cell, seed, seconds, trace, t_process, info)
