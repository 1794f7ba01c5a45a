"""Measured Pallas autotuner with a persistent tuning cache.

TVM's lesson (PAPERS.md): *measured* schedule search beats hand-picked
block shapes.  The repo already owns the two halves this module joins —
``auto_tuner.run_timed_trial`` (the ONE timing protocol) and the
``_common`` block-override registry every kernel's ``pick_row_block``
consults — so tuning a kernel is: time each candidate via the shared
protocol, persist the winner, install it through the registry.

**Cache key.**  Like the structure cache, entries are keyed by a blake2b
fingerprint over everything that invalidates a measurement: kernel name,
argument shapes, dtypes, chip preset, quant layout and ``jax.__version__``
(a new compiler may pick different layouts — stale schedules must
re-measure, never silently load).  The cache file is JSON at
``$PADDLE_TPU_TUNE_CACHE`` (default ``<checkout>/.jax_cache/
tuning_cache.json``), written atomically (tmp + rename) so a crashed
trial never truncates previous winners.

**Round-trip contract** (``tests/test_autotune_cache.py``): the first
run measures every candidate and persists the winner; a second run with
the same key loads it with ZERO ``run_timed_trial`` calls — proven by
the ``hits``/``misses``/``measure_seconds`` telemetry ``bench.py``
surfaces as ``extra.serve.tuning_cache``.  A key change (dims, dtype,
chip, jax) is a miss and re-measures.

**Cost-model feedback.**  Measured entries flow back into
``cost_model.kernel_cost``: a sheet whose kernel+chip matches a cache
entry gains ``measured_ms`` and ``cost_source="measured"`` next to the
analytic roofline (``collective.roofline_ms``), and ``tools/
perf_gate.py`` bounds the predicted-vs-measured ratio both directions
(``PERF_GATE_KERNEL_PRED_TOL_X``).

Escape hatch: ``PADDLE_TPU_TUNE=0`` skips measurement entirely (cache
hits still install — loading a persisted winner costs nothing).
"""

from __future__ import annotations

import hashlib
import json
import os
import time

from ... import REPO_ROOT
from ...cost_model.collective import chip_name
from . import _common as kern
from .decode_layer_pallas import BLOCK_I_KEY, decode_layer, use_kernel

_CACHE_ENV = "PADDLE_TPU_TUNE_CACHE"
_TUNE_ENV = "PADDLE_TPU_TUNE"


def _metrics():
    from ...observability import counter
    return (
        counter("paddle_tpu_tuning_cache_hits_total",
                "Tuning-cache lookups served without measurement"),
        counter("paddle_tpu_tuning_cache_misses_total",
                "Tuning-cache lookups that required measured trials"),
    )


def tuning_enabled() -> bool:
    """Measurement gate (cache *hits* load regardless — only new trials
    are skippable)."""
    return os.environ.get(_TUNE_ENV, "1") != "0"


def kernel_fingerprint(kernel, shapes=(), dtypes=(), chip=None,
                       quant=None, extra=None) -> str:
    """Cache key: blake2b over every measurement invalidator (kernel
    name + shapes + dtypes + chip preset + quant layout + jax version).
    Keyed like the structure cache — same digest size, same "changed
    input means changed key, never a stale read" rule."""
    import jax
    chip = chip_name(chip)
    payload = repr((str(kernel), tuple(tuple(s) for s in shapes),
                    tuple(str(d) for d in dtypes), str(chip),
                    str(quant), extra, jax.__version__))
    return hashlib.blake2b(payload.encode(), digest_size=16).hexdigest()


class TuningCache:
    """JSON-persisted winners plus session telemetry.

    ``get``/``put`` count hits/misses; ``add_measure_seconds`` tracks
    wall time spent in trials so ``bench.py``'s ``tuning_cache`` block
    can prove the second run cost nothing."""

    def __init__(self, path=None):
        self.path = path or os.environ.get(_CACHE_ENV) or os.path.join(
            REPO_ROOT, ".jax_cache", "tuning_cache.json")
        self.hits = 0
        self.misses = 0
        self.measure_seconds = 0.0
        self._entries = None

    def _load(self) -> dict:
        if self._entries is None:
            try:
                with open(self.path, encoding="utf-8") as f:
                    data = json.load(f)
                self._entries = dict(data) if isinstance(data, dict) else {}
            except (OSError, ValueError):
                self._entries = {}
        return self._entries

    def get(self, key):
        entry = self._load().get(key)
        hits, misses = _metrics()
        if entry is None:
            self.misses += 1
            misses.inc()
        else:
            self.hits += 1
            hits.inc()
        return entry

    def peek(self, key):
        """Lookup without touching the hit/miss telemetry."""
        return self._load().get(key)

    def put(self, key, entry) -> None:
        entries = self._load()
        entries[str(key)] = entry
        tmp = f"{self.path}.tmp.{os.getpid()}"
        try:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(entries, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)  # atomic: crash never truncates
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def add_measure_seconds(self, seconds: float) -> None:
        self.measure_seconds += float(seconds)

    def entries(self) -> dict:
        return dict(self._load())

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "measure_seconds": round(self.measure_seconds, 6),
                "entries": len(self._load()), "path": self.path}


_DEFAULT_CACHE: TuningCache | None = None


def default_cache() -> TuningCache:
    """Process-wide cache. Re-created when ``$PADDLE_TPU_TUNE_CACHE``
    changes (tests point it at a tmpdir)."""
    global _DEFAULT_CACHE
    want = os.environ.get(_CACHE_ENV)
    if _DEFAULT_CACHE is None or \
            (want and _DEFAULT_CACHE.path != want):
        _DEFAULT_CACHE = TuningCache()
    return _DEFAULT_CACHE


def stats() -> dict:
    return default_cache().stats()


def _block_i_candidates(i_size: int):
    """The decode-layer search space: MLP column-chunk widths that are
    divisors of the intermediate size AND multiples of 8 (the Mosaic
    sublane rule ``set_block_override`` enforces), largest first so the
    un-chunked layout is always candidate #0."""
    cands = [c for c in (i_size, 1024, 512, 256, 128, 64, 32, 16, 8)
             if c <= i_size and i_size % c == 0 and c % 8 == 0]
    return sorted(set(cands), reverse=True)


def tune_decode_layer(b, h, h_kv, d, page_size, n_pages, hd, i_size,
                      dtype="float32", quant=None, chip=None, cache=None,
                      trial=None, steps=2, warmup=1):
    """Search ``block_i`` for the fused decode layer at the given serving
    shape; persist and install the winner.

    Cache hit: install the stored ``block_i`` via the override registry,
    zero trials.  Miss (and tuning enabled): run every candidate through
    ``run_timed_trial`` on synthetic on-device inputs at the REAL
    shapes, persist ``{block_i, ms, timings, ...}``, install the winner.
    Returns the entry, or ``None`` when the kernel is unavailable /
    measurement is disabled on a miss."""
    import jax
    import jax.numpy as jnp

    from ...auto_tuner.tuner import run_timed_trial
    cache = cache or default_cache()
    trial = trial or run_timed_trial
    shapes = ((b, h, d), (n_pages, h_kv, page_size, d), (b, hd),
              (hd, i_size))
    key = kernel_fingerprint("block_decode_layer", shapes, (dtype,),
                             chip=chip, quant=quant)
    entry = cache.get(key)
    if entry is not None:
        kern.set_block_override(BLOCK_I_KEY, int(entry["block_i"]))
        return entry
    if not tuning_enabled():
        return None
    if not use_kernel((b, h, d), (n_pages, h_kv, page_size, d), n_pages,
                      hd, i_size, dtype):
        return None

    key_fn = jax.random.PRNGKey(0)
    ks = jax.random.split(key_fn, 8)
    f = jnp.dtype(dtype)
    q = jax.random.normal(ks[0], (b, h, d), f)
    kl = jax.random.normal(ks[1], (n_pages, h_kv, page_size, d), f)
    vl = jax.random.normal(ks[2], (n_pages, h_kv, page_size, d), f)
    tab = jnp.tile(jnp.arange(n_pages, dtype=jnp.int32)[None],
                   (b, 1))[:, :n_pages]
    pos = jnp.full((b,), page_size * n_pages - 1, jnp.int32)
    hres = jax.random.normal(ks[3], (b, hd), f)
    wo = jax.random.normal(ks[4], (h * d, hd), f) * 0.02
    wg = jax.random.normal(ks[5], (hd, i_size), f) * 0.02
    wu = jax.random.normal(ks[6], (hd, i_size), f) * 0.02
    wd = jax.random.normal(ks[7], (i_size, hd), f) * 0.02
    norm = jnp.ones((hd,), f)
    interp = kern.interpret_mode()

    timings = {}
    t0 = time.perf_counter()
    for c in _block_i_candidates(i_size):
        def step(qx, c=c):
            y, _ = decode_layer(qx, kl, vl, tab, pos, hres, wo, norm, wg,
                                wu, wd, norm, block_i=c, interpret=interp)
            return jnp.sum(y)  # scalar for the trial's read-back drain
        timings[c] = trial(step, (q,), steps=steps, warmup=warmup)
    cache.add_measure_seconds(time.perf_counter() - t0)

    best = min(timings, key=timings.get)
    entry = {
        "kernel": "block_decode_layer",
        "chip": chip_name(chip),
        "block_i": int(best),
        "ms": timings[best] * 1e3,
        "timings_ms": {str(c): t * 1e3 for c, t in timings.items()},
        "shapes": [list(s) for s in shapes],
        "dtype": str(dtype), "quant": quant,
        "measured_at": time.time(),
    }
    cache.put(key, entry)
    kern.set_block_override(BLOCK_I_KEY, int(best))
    return entry


def tune_for_serving(serving_model, page_size, num_pages, max_pages,
                     max_batch, cache=None, trial=None):
    """Engine hook: derive the decode shape from a ``ServingModel`` and
    tune (or cache-load) before the decode program is built — the
    winner must be installed before the ONE decode trace."""
    m = serving_model
    layer = m.model.layers[0]
    hd = int(m.model.embed_tokens.weight.shape[1])
    i_size = int(layer.mlp.gate_proj.weight.shape[1])
    dtype = "float32"
    return tune_decode_layer(
        int(max_batch), m.n_head, m.n_kv, m.head_dim,
        int(page_size), int(max_pages), hd, i_size, dtype=dtype,
        quant=m._quant_dtype if m._qweights else None,
        cache=cache, trial=trial)


def lookup_measured(kernel, chip=None, cache=None):
    """Most recent cache entry for a kernel name on a chip — the
    cost-model join (``kernel_cost`` prefers this measured ms over the
    analytic roofline). Telemetry-neutral (peeks, never counts)."""
    cache = cache or default_cache()
    chip = chip_name(chip)
    best = None
    for entry in cache.entries().values():
        if not isinstance(entry, dict):
            continue
        if entry.get("kernel") != kernel or entry.get("chip") != chip:
            continue
        if best is None or entry.get("measured_at", 0) > \
                best.get("measured_at", 0):
            best = entry
    return best
