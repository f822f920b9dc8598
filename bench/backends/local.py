"""One replica: a paged ``ServingEngine`` behind a ``LocalEngineBackend``
and a ``Dispatcher`` that admits as many calls as the engine has slots,
on the chip the weights were made on.

A builder takes the model, its weights, the configuration file, the
tokenizer and the cell's devices, and returns the dispatcher that the
window's programs call, with the engines and backends behind it (the
harness observes every engine).  A configuration names its builder under
``"backend"``.
"""


def engine_kwargs(conf: dict) -> dict:
    e = conf["engine"]
    return dict(max_slots=e["max_slots"], max_len=e["max_len"],
                page_size=e["page_size"], num_pages=e["num_pages"],
                prefill_buckets=tuple(e["prefill_buckets"]),
                # the trie may hold every page of the pool; admission
                # reclaims its least recently used leaves when short
                prefix_cache_budget=1 << 62)


def build(model, params, conf, tokenizer, devices):
    from repro.dispatch import AdmissionPolicy, Dispatcher
    from repro.serving import LocalEngineBackend, ServingEngine

    del devices                    # the weights already lie on the one chip
    engine = ServingEngine(model, params, **engine_kwargs(conf))
    backend = LocalEngineBackend(engine, tokenizer)
    dispatcher = Dispatcher([backend], admission=AdmissionPolicy(
        max_concurrency=engine.max_slots))
    return {"dispatcher": dispatcher, "engines": [engine],
            "backends": [backend]}
