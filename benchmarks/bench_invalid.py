"""Paper Fig 5: proportion of invalid (hallucinated) items generated
WITHOUT the valid-path constraint, vs WITH xBeam filtering."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_fn
from repro.config import GRConfig
from repro.configs import get_config
from repro.core import GRDecoder, ItemTrie
from repro.data import gen_catalog
from repro.models import get_model
from repro.launch.compile_cache import enable_compile_cache


def main():
    cfg = get_config("onerec-0.1b").reduced()
    gr = GRConfig(beam_width=16, top_k=16, num_decode_phases=3,
                  num_items=3000, tid_vocab=cfg.vocab_size)
    catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
    trie = ItemTrie(catalog, cfg.vocab_size)
    valid = {tuple(r) for r in catalog.tolist()}
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    R, S = 4, 48
    tokens = jax.random.randint(jax.random.PRNGKey(1), (R, S), 0,
                                cfg.vocab_size)
    lengths = jnp.full((R,), S, jnp.int32)

    for name, t in (("nofilter", None), ("filtered", trie)):
        dec = GRDecoder(cfg, gr, t)
        gen = lambda: dec.generate(params, tokens, lengths, mode="graph")
        dt = time_fn(gen, iters=3, warmup=1)
        out = gen()
        items = np.asarray(out["items"]).reshape(-1, 3)
        frac_invalid = np.mean([tuple(i) not in valid for i in items])
        row(f"fig5_{name}", dt * 1e6,
            f"invalid_frac={frac_invalid*100:.1f}%"
            f";items={items.shape[0]}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
