"""End-to-end behaviour test for the whole system: a PopPy compound-AI
program (the paper's contribution) drives the continuous-batching serving
engine (the substrate) over a real JAX model — parallel `@unordered` LLM
calls must (1) produce results identical to sequential Python execution,
(2) keep ordered externals in order, and (3) actually share decode
batches on the engine."""


import jax


def test_end_to_end_poppy_over_serving_engine():
    from repro.configs import get_config
    from repro.core import poppy, recording, sequential, sequential_mode
    from repro.core.ai import llm, use_backend
    from repro.models import build_model
    from repro.serving import LocalEngineBackend, ServingEngine

    cfg = get_config("stablelm-3b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(11))

    log = []

    @sequential
    def emit(line):
        log.append(line)
        return None

    @poppy
    def pipeline(n):
        drafts = tuple()
        for i in range(n):
            d = llm(f"draft section {i}", max_tokens=3)
            emit(f"section {i}: {len(d)} chars")
            drafts += (d,)
        merged = llm(f"merge {len(drafts)} sections", max_tokens=3)
        emit("merged")
        return (drafts, merged)

    def run(mode):
        log.clear()
        engine = ServingEngine(model, params, max_slots=4, max_len=48)
        with use_backend(LocalEngineBackend(engine)), recording() as tr:
            if mode == "plain":
                with sequential_mode():
                    out = pipeline(3)
            else:
                out = pipeline(3)
        occupancy = engine.max_occupancy
        return out, list(log), tr, occupancy

    out_plain, log_plain, tr_plain, _ = run("plain")
    out_poppy, log_poppy, tr_poppy, occ = run("poppy")

    # deterministic greedy decode ⇒ identical results and ordered output
    assert out_plain == out_poppy
    assert log_plain == log_poppy
    from repro.core import equivalent
    ok, why = equivalent(tr_plain, tr_poppy)
    assert ok, why
    # opportunistic execution really batched the draft calls together
    assert occ >= 2, f"no decode-batch sharing (max occupancy {occ})"
