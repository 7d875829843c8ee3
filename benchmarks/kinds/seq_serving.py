"""Configuration kind `seq-serving`: the session app's `/recommend-next`
through ServingLayer over HTTP with a block-diffusion mixture-of-experts
encoder (`sdar_moe`), one process holding the chip, load from a generator
process (benchmarks/seqgen.py).

The model is synthetic: standard normal x 0.02 in bfloat16 from --seed, the
layers' tensors made on the device, the item catalog (the output head
`E_out`) drawn on the host, both adopted as an artifact's tensors would be
(`apps/seq/state.py adopt_model`), no update-topic replay. The server is the
program as it ships: default reference.conf plus what a read-only server on
mem:// brokers with port 0 needs.

Also here, because later PRs may not change them: the kind's own copy of the
plain float32 reference of the block (`ref_*`), the comparison that decides
`correct` (`replay`, `summarise`), and the functions that compute the
operations and bytes of a step and of its expert layer (`step_work`,
`moe_work`).
"""

from __future__ import annotations

import math
import time
from functools import partial

import numpy as np

from benchmarks.kinds import _encoder
from benchmarks.kinds._encoder import holds  # noqa: F401 - the kind's tests and its siblings read it here

# What `correct` holds the served answers to, against the float32 reference
# replaying the served trajectory. A served score is the float32 dot, on
# the host, of the block position's hidden state with the item's row, so it
# differs from the reference's logit by the hidden state's error alone. All
# distances are in units of the position's largest |logit| over the catalog;
# a position's `score_err` is the root mean square over its candidates.
#
# The hidden state carries two kinds of error. ROUNDING: the activations
# enter every product in the stated dtype (bfloat16: 2^-9 relative; float32:
# the order of accumulation alone), which reaches every position of every
# request alike. ROUTING: where a token's k-th and (k+1)-th router
# probabilities lie closer than that rounding, the served path and the
# reference pick different experts, a step and not a rounding (one expert's
# weighted output at one token of one layer), which reaches SOME positions
# and is no fault: the two experts' weights are then equal to within the
# rounding. So the tight limits are held by a QUARTILE over the sampled
# requests (a fault in the arithmetic moves every request; a routing step
# moves a minority), and the loose limit by the worst.
#
# How large the rounding is depends on the model the seed draws (the quartile
# read 0.86-1.36e-3 over four seeds on the chip, PR 33) and what int8 experts
# add (0.96e-3 in quadrature) lies inside that range, so no fixed distance
# from the float32 reference separates them. The reference is therefore
# computed a second time WITH the stated rounding (the same plain forward,
# every product's inputs rounded to the configuration's dtype where the
# configuration says activations are rounded: `ref_block_hidden(..., act=...)`,
# compiled without XLA's excess precision), and the served scores are held
# to THAT as well: `stated_err`. The sound program reads 2.8-3.3e-4 there
# (on the CPU 1e-7: what is left on the chip is the chip's own arithmetic),
# int8 experts 9.3e-4.
REFERENCE_BATCH = 16   # forwards a reference dispatch (vmap)
# quartile over the requests, each block position: float32 leaves the order
# of accumulation alone; for bfloat16 it is the net that catches a missing
# expert or a wrong mask, between the sound program's 0.8-1.2e-3 and 6.9e-3
# with one expert short (PERF.md)
SCORE_TIGHT = {"float32": 2.0e-5, "bfloat16": 2.5e-3}
# the served scores' distance from the reference WITH the stated rounding,
# the same quartile: between the sound program's 3.3e-4 and int8 experts'
# 9.3e-4 (my chip runs, PR 33; PERF.md has every reading)
STATED_TIGHT = 5.5e-4
SCORE_LOOSE = 5.0e-2   # the worst position: a routing step or two, not a missing expert
MIN_OVERLAP = 9        # of 10 candidates the reference's, by the same quartile
MIN_OVERLAP_WORST = 7  # and in the worst position
REFERENCE_BLOCK_ROWS = 32768
SCOPES = ("sdar.moe", "sdar.attn", "sdar.head")
PROGRAMS = {"prefill": "jit_prefill", "denoise": "jit_denoise_step"}


# -- the algorithm's operations and bytes ------------------------------------------

def moe_work(tokens: float, touched: float, cfg: dict, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) ONE expert layer needs for `tokens` real tokens that
    reach `touched` distinct experts: the router and experts_per_token
    experts' three products a token; each touched expert's three matrices
    and the router read once, the tokens' hidden states read and written in
    float32. Padding tokens, padded row tiles and untouched experts are the
    implementation's, not the algorithm's."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    flops = tokens * (2.0 * h * e + k * 3 * 2.0 * h * f)
    moved = touched * 3.0 * h * f * itemsize + h * e * itemsize + tokens * h * 8.0
    return flops, moved


def step_work(tokens: float, context: float, head_tokens: float, cfg: dict) -> float:
    """FLOPs the MODEL needs for one dispatch of `tokens` real tokens that
    each attend over `context` positions on average, `head_tokens` of which
    also take logits over the catalog (a denoise step's block positions):
    the layers' projections, scores and values, router and routed experts,
    and the output head."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    proj = 2.0 * h * (q + 2 * kv) + 2.0 * q * h
    attend = 2.0 * 2.0 * q * context
    moe = moe_work(1.0, 0.0, cfg)[0]
    layers = cfg["num_hidden_layers"] * tokens * (proj + attend + moe)
    return layers + head_tokens * 2.0 * h * (cfg["vocab_size"] - 1)


# -- the plain reference: float32, `highest`, full forward passes, no cache ---------

def _ref_norm(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _ref_rope(x, pos, theta):
    """x [T, heads, d]: rotate-half over the whole head, absolute positions."""
    import jax.numpy as jnp

    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + rot * sin


def _as(x, act):
    """x at the values dtype `act` holds, still float32 (None: as it is)."""
    import jax.numpy as jnp

    return x if act is None else x.astype(act).astype(jnp.float32)


def _ref_moe(u, layer, k, act=None):
    """Every expert in turn on every token, weighted by the token's
    normalised routing weight for it (zero unless one of its k). The router
    reads the float32 `u`; the experts' products take their inputs at `act`."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    p = jax.nn.softmax(u @ layer["router"].astype(f32), axis=-1)
    top, which = jax.lax.top_k(p, k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.zeros_like(p).at[jnp.arange(u.shape[0])[:, None], which].add(top)

    ua = _as(u, act)

    def expert(acc, xs):
        wg, wu, wd, col = xs
        mid = _as(jax.nn.silu(ua @ wg.astype(f32)) * (ua @ wu.astype(f32)), act)
        return acc + col[:, None] * (mid @ wd.astype(f32)), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(u), (layer["wg"], layer["wu"], layer["wd"], weight.T)
    )
    return out


def ref_block_hidden(cfg: dict, params: dict, tokens, n_prefix, act=None):
    """tokens [T] = the prefix, one block of B positions, then padding ->
    the block's final-normed hidden states [B, H]. The prefix is causal
    within itself; the block sees the whole prefix and ALL of itself; the
    padding is seen by nobody. `n_prefix` may be traced.

    `act` None is THE reference: float32 throughout. With a dtype it is the
    same forward with the configuration's stated rounding: every product's
    inputs (the normalised stream, q and k after norm and RoPE, the
    probabilities, v, the attention's output, the experts' input and their
    middle) at that dtype's values, accumulated in float32; the stream, the
    norms, the softmaxes and the router stay float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    heads, kv_heads, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta, block = cfg["rms_norm_eps"], float(cfg["rope_theta"]), cfg["block_length"]
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[0]
        pos = jnp.arange(t)
        in_block = (pos >= n_prefix) & (pos < n_prefix + block)
        allowed = (pos[None, :] <= pos[:, None]) | (in_block[:, None] & in_block[None, :])
        x = params["E_in"][tokens].astype(f32)
        for layer in params["layers"]:
            u = _as(_ref_norm(x, layer["ln1"].astype(f32), eps), act)
            q = (u @ layer["wq"].astype(f32)).reshape(t, heads, d)
            k = (u @ layer["wk"].astype(f32)).reshape(t, kv_heads, d)
            v = (u @ layer["wv"].astype(f32)).reshape(t, kv_heads, d)
            q = _as(_ref_rope(_ref_norm(q, layer["q_norm"].astype(f32), eps), pos, theta), act)
            k = _as(_ref_rope(_ref_norm(k, layer["k_norm"].astype(f32), eps), pos, theta), act)
            k = jnp.repeat(k, heads // kv_heads, axis=1)  # query head j reads kv head j // group
            v = jnp.repeat(_as(v, act), heads // kv_heads, axis=1)
            s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(d)
            s = jnp.where(allowed[None], s, -jnp.inf)
            prob = _as(jax.nn.softmax(s, axis=-1), act)
            o = jnp.einsum("hts,shd->thd", prob, v).reshape(t, heads * d)
            x = x + _as(o, act) @ layer["wo"].astype(f32)
            u = _ref_norm(x, layer["ln2"].astype(f32), eps)
            x = x + _ref_moe(u, layer, cfg["num_experts_per_tok"], act)
        z = _ref_norm(x, params["final_norm"].astype(f32), eps)
        return jax.lax.dynamic_slice_in_dim(z, n_prefix, block, axis=0)


def ref_logits(zb, e_out) -> np.ndarray:
    """[B, H] hidden x the catalog `e_out` [items, H] (any float dtype, on
    the device) -> float32 logits [B, items] at `highest`, in row blocks."""
    import jax
    import jax.numpy as jnp

    out = np.empty((zb.shape[0], e_out.shape[0]), dtype=np.float32)
    with jax.default_matmul_precision("highest"):
        for lo in range(0, e_out.shape[0], REFERENCE_BLOCK_ROWS):
            rows = e_out[lo:lo + REFERENCE_BLOCK_ROWS].astype(jnp.float32)
            out[:, lo:lo + rows.shape[0]] = np.asarray(jnp.dot(zb, rows.T))
    return out


# -- the comparison that decides `correct` --------------------------------------------

def trajectory(cfg: dict, answer: list, session: np.ndarray) -> list[np.ndarray] | None:
    """The token arrays (prefix + block) the reference is asked at steps
    0..T-1 of a served answer: [MASK] where the served path had fixed
    nothing yet, the served items where it had; None where the answer's
    form is wrong."""
    block, steps = cfg["block_length"], cfg["denoise_steps"]
    try:
        fixed = [int(e["item"][1:]) for e in answer]
        at = [int(e["step"]) for e in answer]
    except (ValueError, TypeError, KeyError, IndexError):
        return None
    if len(fixed) != block or sorted(at) != list(range(steps)):
        return None
    n = len(session)
    tokens = np.full(n + block, cfg["vocab_size"] - 1, dtype=np.int32)
    tokens[:n] = session
    out = []
    for step in range(steps):
        out.append(tokens.copy())
        b = at.index(step)
        tokens[n + b] = fixed[b]  # announced id j is E_in row j
    return out


def replay(
    cfg: dict, answer: list, session: np.ndarray, block_logits, how_many: int, rounded_logits=None,
) -> list[dict]:
    """One served answer against the reference replaying ITS trajectory:
    at step s the reference takes logits with the block as the served path
    left it (`block_logits(tokens) -> [B, items]`), compares the position the
    served path fixed at s, then fixes it to the served item. One dict a
    block position: {"fault", "score_err", "rounding", "fixed_gap",
    "overlap", "candidate_gap"}, the distances in units of the position's
    largest |logit|; numbers None where the form is wrong. `rounding` is
    the same distance as `score_err`, at the same candidates, of
    `rounded_logits(tokens)` (the reference with the stated rounding) from
    the float32 reference; None without one."""
    out = [
        {"fault": None, "score_err": None, "rounding": None, "stated_err": None, "fixed_gap": None,
         "overlap": None, "candidate_gap": None}
        for _ in range(cfg["block_length"])
    ]
    asked = trajectory(cfg, answer, session)
    try:
        fixed = [int(e["item"][1:]) for e in answer]
        at = [int(e["step"]) for e in answer]
        pages = [[(int(i[1:]), float(s)) for i, s in e["next"]] for e in answer]
    except (ValueError, TypeError, KeyError, IndexError):
        asked = None
    if asked is None:
        for o in out:
            o["fault"] = "not one entry a block position with the steps 0..T-1"
        return out
    for step, tokens in enumerate(asked):
        b = at.index(step)
        logits = block_logits(tokens)[b]
        scale = float(np.max(np.abs(logits)))
        rows = [r for r, _ in pages[b]]
        got = np.asarray([s for _, s in pages[b]], dtype=np.float64)
        o = out[b]
        if len(rows) != how_many:
            o["fault"] = f"{len(rows)} candidates served, not {how_many}"
        elif set(rows) & set(session.tolist()):
            o["fault"] = "an item of the session was served"
        elif np.any(np.diff(got) > 0):
            o["fault"] = "scores not descending"
        else:
            o["score_err"] = float(np.sqrt(np.mean((got - logits[rows]) ** 2))) / scale
            if rounded_logits is not None:
                low = rounded_logits(tokens)[b]
                o["rounding"] = float(np.sqrt(np.mean((low[rows] - logits[rows]) ** 2))) / scale
                o["stated_err"] = float(np.sqrt(np.mean((got - low[rows]) ** 2))) / scale
            o["fixed_gap"] = float(np.max(logits) - logits[fixed[b]]) / scale
            open_ = logits.copy()
            open_[session] = -np.inf
            ref_top = np.argsort(-open_, kind="stable")[:how_many]
            o["overlap"] = len(set(rows) & set(ref_top.tolist()))
            o["candidate_gap"] = float(max(0.0, open_[ref_top[-1]] - min(open_[r] for r in rows))) / scale
    return out


def summarise(per_request: list[list[dict]], dtype: str = "bfloat16") -> dict:
    """The compared numbers of `replay`'s readings over the sampled requests,
    under this kind's limits."""
    return _encoder.summarise(
        per_request, SCORE_TIGHT[dtype], STATED_TIGHT, SCORE_LOOSE, MIN_OVERLAP, MIN_OVERLAP_WORST
    )


# -- the model from the seed ----------------------------------------------------------------

def to_bfloat16_values(x: np.ndarray) -> np.ndarray:
    """float32 array rounded (to nearest even) to the values bfloat16
    holds, still float32, in place."""
    bits = x.view(np.uint32)
    bits += np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))
    bits &= np.uint32(0xFFFF0000)
    return x


def draw_catalog(seed: int, items: int, features: int) -> np.ndarray:
    """E_out on the host: standard normal x 0.02, at bfloat16's values (the
    published dtype), so the float32 host rows and the bf16 view agree."""
    from benchmarks.kinds.als_serving import draw_factors

    e = draw_factors(seed, 30, items, features)
    e *= np.float32(0.02)
    return to_bfloat16_values(e)


def extensions(config: dict) -> dict:
    """The artifact's extensions: the source's own keys, as strings."""
    keys = (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts",
        "moe_intermediate_size", "num_experts_per_tok", "num_hidden_layers", "vocab_size",
        "rope_theta", "rms_norm_eps", "block_length", "denoise_steps", "max_len", "dtype",
    )
    return dict({k: str(config[k]) for k in keys}, encoder="sdar")


def build(cell: dict, seed: int, info):
    """The model from the seed and the server around it, started:
    (serving, manager, state, e_host). The caller closes `serving`."""
    # a tree without the block fails here, at once, before any set-up
    from oryx_tpu.ops import sdar
    from oryx_tpu.serving import stepper  # noqa: F401

    import jax

    from oryx_tpu.apps.seq.state import adopt_model

    config = cell["config"]
    n_items = config["vocab_size"] - 1  # the last id is [MASK]
    # -- model: the layers on the device, the catalog on the host
    t_build = time.monotonic()
    ext = extensions(config)
    enc = sdar.SdarEncoder.from_extensions(ext.get)
    tensors = sdar.init_tensors(enc.cfg, seed, enc.dtype)
    e_host = draw_catalog(seed, n_items, config["hidden_size"])
    tensors["E"] = e_host
    state = adopt_model(None, ext.get, tensors, [f"i{j}" for j in range(n_items)])
    jax.block_until_ready(state.params)
    info(phase="model_built", seconds=time.monotonic() - t_build,
         parameters=sdar.param_count(enc.cfg) + n_items * config["hidden_size"])
    return (*_encoder.serve(cell, state), state, e_host)


def reference_tables(config: dict, params: dict, e_dev, asked: list[np.ndarray], act) -> dict:
    """{tokens' bytes: logits [B, items]} of the reference for every token
    array in `asked`, REFERENCE_BATCH forwards a dispatch (the arrays are
    independent: the trajectory is the served one)."""
    import jax
    import jax.numpy as jnp

    block, width = config["block_length"], config["max_len"] + config["block_length"]
    # XLA may by default keep MORE precision than asked (it drops a float32 ->
    # bfloat16 -> float32 pair): the stated rounding has to be computed as stated
    forward = jax.jit(
        jax.vmap(partial(ref_block_hidden, config, act=act), in_axes=(None, 0, 0))
    ).lower(
        params, jax.ShapeDtypeStruct((REFERENCE_BATCH, width), jnp.int32),
        jax.ShapeDtypeStruct((REFERENCE_BATCH,), jnp.int32),
    ).compile(compiler_options={"xla_allow_excess_precision": False})
    table = {}
    for lo in range(0, len(asked), REFERENCE_BATCH):
        group = asked[lo:lo + REFERENCE_BATCH]
        padded = np.zeros((REFERENCE_BATCH, width), dtype=np.int32)
        n_prefix = np.zeros((REFERENCE_BATCH,), dtype=np.int32)
        for j, tokens in enumerate(group):
            padded[j, : len(tokens)] = tokens
            n_prefix[j] = len(tokens) - block
        zb = forward(params, jnp.asarray(padded), jnp.asarray(n_prefix))
        logits = ref_logits(zb.reshape(REFERENCE_BATCH * block, -1), e_dev)
        for j, tokens in enumerate(group):
            table[tokens.tobytes()] = logits[j * block:(j + 1) * block]
    return table


def check(config: dict, traffic: dict, state, e_host, served: list) -> tuple[list, int]:
    """The sampled sessions' answers (`served`: [(answer, session)]), each
    against the reference replaying it: (readings of `replay`, the
    reference's forward passes)."""
    import jax.numpy as jnp

    asked = [t for answer, session in served for t in trajectory(config, answer, session) or []]
    e_dev = jnp.asarray(e_host, dtype=jnp.bfloat16)  # bf16 holds E_out's values exactly
    exact = reference_tables(config, state.params, e_dev, asked, None)
    # the configuration's stated rounding, where it states one below float32
    act = None if config["dtype"] == "float32" else jnp.dtype(config["dtype"])
    rounded = reference_tables(config, state.params, e_dev, asked, act) if act is not None else None
    readings = [
        replay(
            config, answer, session, lambda t: exact[t.tobytes()], int(traffic["how_many"]),
            (lambda t: rounded[t.tobytes()]) if rounded is not None else None,
        )
        for answer, session in served
    ]
    return readings, len(asked)


def invariants(config: dict, final: dict, started: dict, sent: list, timed_out: int) -> dict:
    """This kind's own entries of `compared`, since the process began: every
    block took its denoise steps, and every real token of a prefill or a step
    went through every expert layer's k experts."""
    total = lambda series: final.get(series, 0.0)  # noqa: E731
    real_tokens = sum(
        total(f'oryx_seq_step_tokens_total{{kind="{kind}",tokens="real"}}') for kind in PROGRAMS
    )
    expected_pairs = real_tokens * config["num_experts_per_tok"] * config["num_hidden_layers"]
    blocks = total("oryx_seq_blocks_total")
    return {
        "steps_per_block": [total("oryx_seq_denoise_steps_total") / blocks if blocks else None,
                            "==", config["denoise_steps"]],
        "dropped_pairs": [expected_pairs - total("oryx_moe_routed_total"), "==", 0],
    }


def compiled_texts(model) -> dict[str, list[str]]:
    """The compiled text of every encoder program the engine runs, by the
    program's name on the device trace: lowered again from the live arrays'
    shapes (a persistent compile cache makes it a load)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.ops import sdar

    engine = model._engine()
    enc = engine.encoder
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    params, state = jax.tree.map(shape, engine.params), jax.tree.map(shape, engine.state)
    view, _n_valid, row_token = engine.head()
    rows = lambda dt: jax.ShapeDtypeStruct((enc.step_rows,), dt)  # noqa: E731
    texts = {PROGRAMS["prefill"]: [], PROGRAMS["denoise"]: []}
    for bucket in enc.length_buckets:
        p = jax.ShapeDtypeStruct((enc.prefill_rows,), jnp.int32)
        lowered = sdar.prefill.lower(
            enc.cfg, params, state, jax.ShapeDtypeStruct((enc.prefill_rows, bucket), jnp.int32), p, p
        )
        texts[PROGRAMS["prefill"]].append(lowered.compile().as_text())
    lowered = sdar.denoise_step.lower(
        enc.cfg, params, state, shape(view), jax.ShapeDtypeStruct((), jnp.int32), shape(row_token),
        rows(jnp.int32), rows(jnp.int32), rows(jnp.bool_), rows(jnp.int32),
    )
    texts[PROGRAMS["denoise"]].append(lowered.compile().as_text())
    return texts


KIND = _encoder.Kind(
    name="seq_serving", programs=PROGRAMS, scopes=SCOPES, build=build, check=check, summarise=summarise,
    invariants=invariants, compiled_texts=compiled_texts, position="block", reserved_ids=1,
)


def run(cell: dict, seed: int, seconds: float, trace: bool, t_process: float, info) -> dict:
    """One run of one cell. `cell` = {name, config, traffic, chips, scratch}."""
    return _encoder.run(KIND, cell, seed, seconds, trace, t_process, info)
