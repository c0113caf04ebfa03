"""A whole run over two replicas, each on its own device, behind the
program's router: the cell's ``chips`` give the replicas, every replica
serves, its cache hits count, and the check compares answers of each
replica with the reference; with a token altered where it is produced,
the run is not correct; and warm-up builds every replica's programs
whatever the router does with them.  The run
needs two devices at process start, so it runs in a subprocess over two
forced host devices; this file is its own worker
(``python bench/tests/test_replicas.py run|fault|pinned``)."""

import os
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _worker_says(mode: str, word: str) -> None:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    proc = subprocess.run([sys.executable, os.path.abspath(__file__), mode],
                          env=env, cwd=ROOT, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr[-4000:]
    assert word in proc.stdout, proc.stdout + proc.stderr[-4000:]


def test_two_replicas_serve_and_their_cache_hits_are_summed():
    _worker_says("run", "REPLICAS OK")


def test_a_token_altered_on_the_replicas_is_not_correct():
    _worker_says("fault", "FAULT CAUGHT")


def test_every_replica_builds_its_programs_whatever_the_router():
    _worker_says("pinned", "EVERY REPLICA WARM")


def _router_to_first() -> None:
    """A router that places every request on the first replica it is
    offered, as one that follows a shared prefix would place a warm-up
    burst."""
    from repro.serving.replica import ReplicaRouter
    place = ReplicaRouter.place

    def to_first(self, state):
        every = self.replicas
        self.replicas = every[:1]
        try:
            return place(self, state)
        finally:
            self.replicas = every

    ReplicaRouter.place = to_first


def _compared_prompts(spec) -> list:
    """Record the prompt of every request the check compares with the
    reference."""
    seen, reference = [], spec.reference

    def recording(cfg):
        mod = reference(cfg)
        logprobs = mod.logprobs

        def wrapped(params, c, prompt, *a, **k):
            seen.append(np.asarray(prompt).tobytes())
            return logprobs(params, c, prompt, *a, **k)

        mod.logprobs = wrapped
        return mod

    spec.reference = recording
    return seen


def _alter_last_token() -> None:
    """Every replica's decoder returns its last phase's tokens shifted by
    one, as ``test_correctness.py`` plants it on one engine."""
    from repro.core.gr_decode import GRDecoder
    select = GRDecoder._beam_select

    def altered(self, state, logits, d):
        st, parent = select(self, state, logits, d)
        if d == self.gr.num_decode_phases - 1:
            tok = st.tokens.at[:, :, d].set(
                (st.tokens[:, :, d] + 1) % self.cfg.vocab_size)
            st = type(st)(tok, st.log_probs, st.step, st.prefix_ids,
                          st.pruned)
        return st, parent

    GRDecoder._beam_select = altered


def _worker(mode: str) -> None:
    from bench import harness, spec
    import jax
    assert len(jax.devices()) == 2
    if mode == "fault":
        _alter_last_token()
    cfg = spec.load_json("bench/tests/data/tiny.json")
    traffic = spec.load_json("bench/tests/data/tiny_sessions.json")
    if mode == "pinned":
        _warm_under_a_router_that_takes_the_first(harness, spec, cfg,
                                                  traffic)
        return
    compared = _compared_prompts(spec)
    runs = []
    read = spec.reader

    def keep(name):
        def reader(run):
            runs.append(run)
            return read(name)(run)
        return reader

    spec.reader = keep
    cell = spec.Cell("tiny.replicas2", 2, cfg, traffic,
                     [spec.Metric("latency_p50_ms", "ms", "lower",
                                  "host_clock"),
                      spec.Metric("setup_s", "s", "lower", "host_clock")],
                     [])
    out = harness.run_cell(cell, 2**31 + 11, 2.0, False, time.perf_counter(),
                           spec.peaks("TPU v5 lite"))
    if mode == "fault":
        assert not out["correct"], out["checks"]
        print("FAULT CAUGHT")
        return
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    run = runs[0]
    replica = {rid: s.replica for s in run.steps for rid in s.rids}
    served = {replica[r.rid] for r in run.requests}
    assert served == {0, 1}, served
    hits = [0, 0]
    for t, rid, _, hit in run.probes:
        if run.t_open <= t < run.t_close:
            hits[replica[rid]] += hit
    print("hit tokens per replica", hits, "summed", run.cache_hit_tokens)
    assert run.cache_hit_tokens == sum(hits)
    assert min(hits) > 0
    # the driver's record of placement agrees with the steps
    assert all(r.replica == replica[r.rid] for r in run.requests)
    same = read("same_replica_share")(run)
    print("same replica share", same)
    assert same is not None and 0.0 <= same <= 100.0
    # the check compared at least one request of each replica
    checked = {r.replica for r in run.requests
               if r.prompt.tobytes() in compared}
    assert checked == {0, 1}, checked
    print("REPLICAS OK")


def _warm_under_a_router_that_takes_the_first(harness, spec, cfg, tr):
    """Warm-up places its requests on each replica itself, so every
    replica builds every program even when the router would send them all
    to one."""
    from bench import traffic
    from bench.driver import Driver
    _router_to_first()
    cell = spec.Cell("tiny.replicas2", 2, cfg, tr, [], [])
    world = harness.build_world(cell, 2**31 + 12, 2.0)
    system, _ = harness.build_system(world, 2)
    driver = Driver(system)
    hist = traffic.HistorySampler(world.catalog, world.seeds["warm"] + 7)
    harness.warm_shapes(driver, cell, hist)
    built = [set(r.engine._compiled) for r in system.replicas]
    print("programs per replica", [len(b) for b in built])
    assert built[0] and built[0] == built[1]
    # the router under test still takes the first replica it is offered
    req = driver.submit(world.run.requests[0][1], 0.0)
    assert req.replica == 0
    print("EVERY REPLICA WARM")


if __name__ == "__main__" and sys.argv[1:] in (["run"], ["fault"],
                                              ["pinned"]):
    _worker(sys.argv[1])
