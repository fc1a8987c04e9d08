"""Serving launcher: build a wiki from a corpus, freeze it onto the
device tier (``DeviceEngine``), answer a query batch.

    PYTHONPATH=src python -m repro.launch.serve --queries 8
"""
from __future__ import annotations

import argparse

from repro.configs import get_config
from repro.core.cache import TieredCache
from repro.core.engine import DeviceEngine
from repro.core.oracle import HeuristicOracle
from repro.core.pipeline import ConstructionPipeline, PipelineConfig
from repro.data.corpus import AuthTraceConfig, generate_authtrace
from repro.data.tokenizer import HashTokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.runtime.serving import Request, ServingEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="wikikv-router")
    ap.add_argument("--queries", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    docs, questions = generate_authtrace(
        AuthTraceConfig(n_docs=60, n_questions=max(args.queries, 8),
                        seed=args.seed))
    oracle = HeuristicOracle()
    pipe = ConstructionPipeline(PipelineConfig(), oracle)
    pipe.bootstrap(docs)
    for i in range(0, len(docs), 16):
        pipe.ingest(docs[i:i + 16])

    cfg = get_config(args.arch)
    if cfg.d_model > 512:
        cfg = cfg.reduced()
    tok = HashTokenizer(vocab_size=cfg.vocab).fit([d["text"] for d in docs])
    params = M.init_params(cfg, seed=args.seed)
    cache = TieredCache(pipe.store, bus=pipe.bus)
    cache.prewarm()
    device = DeviceEngine.from_store(pipe.store)
    engine = ServingEngine(cfg, params, tok, device, oracle,
                           cache=cache, batch_size=args.batch_size,
                           max_len=256)
    reqs = [Request(rid=q.qid, query=q.text, max_new_tokens=8)
            for q in questions[: args.queries]]
    done = engine.run(reqs)
    for r in done:
        print(f"[{r.rid}] tool_calls={r.trace.tool_calls} "
              f"pages={r.trace.pages_read} nav={r.latency_s*1000:.1f}ms")
        print(f"    Q: {r.query}")
        print(f"    A: {r.answer[:160]}")
    print(f"cache hit-rate: {cache.stats.hit_rate():.2f}")
    return done


if __name__ == "__main__":
    main()
