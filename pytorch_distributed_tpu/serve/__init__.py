"""serve/ — continuous-batching inference engine (paged KV pool).

The online counterpart of ``generation.generate``: requests arrive,
start, and retire independently while ONE compiled decode step serves
every mix of in-flight work (docs/DESIGN.md §11, §16). KV memory is a
page pool: requests hold page tables, identical prompt prefixes share
pages copy-free via refcounts, and ``SpecConfig`` folds draft-verify
speculative decoding into the engine tick. Quickstart::

    from pytorch_distributed_tpu.serve import (
        EngineConfig, Request, ServeEngine, SpecConfig,
    )

    engine = ServeEngine(model, params, EngineConfig(num_slots=4,
                                                     max_len=256))
    h = engine.submit(Request(prompt_ids, max_new_tokens=64,
                              temperature=0.8, top_p=0.95, seed=7))
    engine.run_until_drained()
    print(h.tokens)   # bit-identical to the solo generate() call

    # speculative decoding: 1..k+1 tokens per tick, greedy streams
    # still bit-identical to the target's own generate()
    engine = ServeEngine(model, params, cfg,
                         spec=SpecConfig(draft_model, draft_params,
                                         num_draft_tokens=4))

    # r18 — disaggregated fleet: prefill tier fills pages and ships
    # them (ring KV migration), decode tier owns the tick, a
    # deterministic Router balances on streamed telemetry, and an
    # InProcPrefixStore prefills shared prompts once per FLEET
    from pytorch_distributed_tpu.serve import Router, InProcPrefixStore
    store = InProcPrefixStore()
    router = Router(
        prefill=[ServeEngine(model, params,
                             EngineConfig(role="prefill",
                                          engine_id=f"p{i}"),
                             prefix_store=store) for i in range(2)],
        decode=[ServeEngine(model, params,
                            EngineConfig(role="decode",
                                         engine_id=f"d{i}"))
                for i in range(2)],
        store=store)
    router.warm_up(prompt_ids)
    h = router.submit(Request(prompt_ids, max_new_tokens=64))
    router.run_until_drained()   # same stream a solo engine emits
"""

from pytorch_distributed_tpu.serve.disagg import (
    MigrationError,
    MigrationFrame,
    decode_frame,
    encode_frame,
    recv_frame,
    roundtrip_frame,
    send_frame,
)
from pytorch_distributed_tpu.serve.engine import (
    EngineConfig,
    ServeEngine,
    SpecConfig,
)
from pytorch_distributed_tpu.serve.prefix_store import (
    InProcPrefixStore,
    PrefixStore,
)
from pytorch_distributed_tpu.serve.router import (
    GaugeBoard,
    Router,
    RouterHandle,
)
from pytorch_distributed_tpu.serve.loadgen import (
    drive,
    prefix_shared_requests,
    uniform_arrivals,
    warm_up,
)
from pytorch_distributed_tpu.serve.kv_slots import (
    PagedKVPool,
    SlotLease,
    auto_page_size,
    extract_frames,
    frame_f32_nbytes,
    frame_nbytes,
    frame_signature,
    gather_pages,
    init_page_cache,
    page_axis,
    scatter_kv,
    splice_frames,
)
from pytorch_distributed_tpu.serve.sampling import (
    filter_logits_rows,
    sample_logits_rows,
)
from pytorch_distributed_tpu.serve.scheduler import (
    PrefillChunk,
    Request,
    RequestHandle,
    RequestStatus,
    Scheduler,
)
from pytorch_distributed_tpu.serve.telemetry import ServeTelemetry

__all__ = [
    "EngineConfig",
    "GaugeBoard",
    "InProcPrefixStore",
    "MigrationError",
    "MigrationFrame",
    "PagedKVPool",
    "PrefillChunk",
    "PrefixStore",
    "Request",
    "RequestHandle",
    "RequestStatus",
    "Router",
    "RouterHandle",
    "Scheduler",
    "ServeEngine",
    "ServeTelemetry",
    "SlotLease",
    "SpecConfig",
    "auto_page_size",
    "decode_frame",
    "drive",
    "encode_frame",
    "extract_frames",
    "filter_logits_rows",
    "frame_f32_nbytes",
    "frame_nbytes",
    "frame_signature",
    "gather_pages",
    "init_page_cache",
    "page_axis",
    "prefix_shared_requests",
    "recv_frame",
    "roundtrip_frame",
    "sample_logits_rows",
    "scatter_kv",
    "send_frame",
    "splice_frames",
    "uniform_arrivals",
    "warm_up",
]
