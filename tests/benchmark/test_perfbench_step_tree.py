"""The readers of the serving loop's span tree
(``perfbench/metrics/_step_tree.py``) on a hand-made window of a dozen
spans, each answer worked out here; and on a program without the tree.

The window is [10, 20) s. Its steps, as the program would record them
(``(name, start_s, end_s, args)``, ids and parents as
``runtime/tracing.py`` writes them):

* step 1, [10.0, 10.1]: a decode tick alone; its fetch waits 90 ms.
* step 2, [10.1, 10.4]: one prefill chunk, final, and a tick; the first
  token's read waits 150 ms, the tick's fetch 100 ms.
* step 3, [10.4, 10.5]: a chunk and no tick (nothing decodes yet).
* step 4, [10.5, 10.62]: a tick alone; fetch 100 ms; an eviction below
  the emit loop (a grandchild of the step).
* step 5, [19.95, 20.05]: straddles the close; left out whole.
* step 6, [9.9, 10.0]... starts before the open; left out.
* step 7, [12.0, 12.001]: an idle step (nothing ran).
"""

import os

import pytest

from perfbench.harness import cells

WINDOW = (10.0, 20.0)


def _span(name, a, b, span_id, parent_id, **args):
    return (name, a, b, dict(args, span_id=span_id, parent_id=parent_id))


def _step(a, b, span_id, chunks, decoded):
    return _span("serve.step", a, b, span_id, None,
                 did=bool(chunks or decoded), prefill_chunks=chunks,
                 decoded=decoded)


SPANS = [
    _span("serve.submit", 10.05, 10.051, 90, None, request="r1"),
    _span("serve.submit", 9.0, 9.001, 91, None, request="r0"),
    # step 1
    _step(10.0, 10.1, 1, 0, 3),
    _span("serve.decode_tick", 10.001, 10.002, 2, 1, active=3, n_pages=8,
          live_pages=12),
    _span("serve.token_fetch", 10.002, 10.092, 3, 1),
    _span("serve.emit", 10.092, 10.099, 4, 1),
    # step 2
    _step(10.1, 10.4, 10, 1, 4),
    _span("serve.admit", 10.101, 10.102, 11, 10, request="r1"),
    _span("serve.prefill_chunk", 10.103, 10.104, 12, 10, request="r1",
          n_pages=8, start=0, final=True),
    _span("serve.first_token_fetch", 10.11, 10.26, 13, 10, request="r1"),
    _span("serve.decode_tick", 10.27, 10.271, 14, 10, active=4, n_pages=16,
          live_pages=20),
    _span("serve.token_fetch", 10.28, 10.38, 15, 10),
    # step 3
    _step(10.4, 10.5, 20, 1, 0),
    _span("serve.prefill_chunk", 10.41, 10.411, 21, 20, request="r2",
          n_pages=8, start=0, final=False),
    # step 4
    _step(10.5, 10.62, 30, 0, 4),
    _span("serve.decode_tick", 10.501, 10.502, 31, 30, active=4, n_pages=16,
          live_pages=22),
    _span("serve.token_fetch", 10.51, 10.61, 32, 30),
    _span("serve.emit", 10.61, 10.619, 33, 30),
    _span("serve.evict", 10.612, 10.613, 34, 33, request="r0",
          status="completed"),
    # step 5: straddles the close of the window
    _step(19.95, 20.05, 40, 0, 4),
    _span("serve.decode_tick", 19.951, 19.952, 41, 40, active=4, n_pages=16,
          live_pages=30),
    _span("serve.token_fetch", 19.96, 20.04, 42, 40),
    # step 6: began before the open
    _step(9.9, 10.0, 50, 0, 2),
    _span("serve.decode_tick", 9.901, 9.902, 51, 50, active=2, n_pages=8,
          live_pages=5),
    _span("serve.token_fetch", 9.91, 9.99, 52, 50),
    # step 7: idle
    _step(12.0, 12.001, 60, 0, 0),
]
REQUESTS = [
    {"id": "r1", "due": 10.04}, {"id": "r0", "due": 8.9},
    {"id": "r9", "due": 11.0},  # due in the window, never admitted
]


def _ctx(spans=SPANS):
    return {"spans": spans, "window": WINDOW, "num_slots": 4,
            "requests": REQUESTS}


def _read(metric, ctx):
    path = os.path.join(cells.HERE, "metrics", f"{metric}.py")
    return cells.load_module(path).read(ctx)


def _tree():
    return cells.load_module(
        os.path.join(cells.HERE, "metrics", "_step_tree.py")
    )


def test_trees_hold_the_window_s_steps_and_their_descendants():
    trees = _tree().step_trees(_ctx())
    # steps 1, 2, 3, 4 and the idle one; 5 and 6 cross an edge
    assert [t[0] for t in trees] == [10.0, 10.1, 10.4, 10.5, 12.0]
    below = trees[3][3]
    # a grandchild (the eviction below the emit loop) belongs to its step
    assert [a["request"] for _, _, a in below["serve.evict"]] == ["r0"]
    assert sorted(below) == ["serve.decode_tick", "serve.emit",
                             "serve.evict", "serve.token_fetch"]
    assert trees[4][3] == {}


@pytest.mark.parametrize("cell", ["sat", "chat"])
def test_step_host_leaves_both_device_waits_out(cell):
    # steps with a tick: 1, 2 and 4 (3 ran none, 7 is idle).
    # 1: 100 - 90 = 10; 2: 300 - 150 (first token) - 100 = 50;
    # 4: 120 - 100 = 20 ms: the median is 20
    assert _read(f"step_host_ms_p50.{cell}", _ctx()) == pytest.approx(20.0)
    # without the first-token span the same step would have read 200
    spans = [s for s in SPANS if s[0] != "serve.first_token_fetch"]
    host = sorted([10.0, 200.0, 20.0])
    assert _read(f"step_host_ms_p50.{cell}", _ctx(spans)) == pytest.approx(
        host[1]
    )


@pytest.mark.parametrize("cell", ["sat", "chat"])
def test_decode_step_is_the_steps_with_a_tick_and_no_chunk(cell):
    # steps 1 and 4: 100 and 120 ms
    assert _read(f"decode_step_ms_p50.{cell}", _ctx()) == pytest.approx(110.0)


@pytest.mark.parametrize("cell", ["sat", "chat"])
def test_prefill_chunk_is_what_a_chunk_adds_to_a_step(cell):
    # step 2: (300 - 110) / 1 = 190; step 3 ran no tick: 100 / 1;
    # the median of the two is 145
    assert _read(f"prefill_chunk_ms_p50.{cell}", _ctx()) == pytest.approx(
        145.0
    )
    # two chunks in step 2 halve what each adds: (95 + 100) / 2
    spans = [
        (n, a, b, dict(args, prefill_chunks=2)) if args["span_id"] == 10
        else (n, a, b, args) for n, a, b, args in SPANS
    ]
    assert _read(f"prefill_chunk_ms_p50.{cell}", _ctx(spans)) == pytest.approx(
        97.5
    )


@pytest.mark.parametrize("cell", ["sat", "chat"])
def test_kv_walk_counts_live_pages_over_the_grid(cell):
    # ticks that began in the window: steps 1, 2, 4 and 5 (its tick
    # began before the close). live 12 + 20 + 22 + 30 = 84 pages of
    # 4 slots x (8 + 16 + 16 + 16) = 224 grid steps
    assert _read(f"kv_walk_useful_share.{cell}", _ctx()) == pytest.approx(
        100.0 * 84 / 224
    )
    assert 100.0 * 84 / 224 == pytest.approx(37.5)


def test_admit_wait_runs_from_the_submit_span_s_end():
    # r1 alone is due in the window and admitted: 10.101 - 10.051
    assert _read("admit_wait_p95_ms.chat", _ctx()) == pytest.approx(50.0)


METRICS = [
    f"{m}.{c}" for m in ("step_host_ms_p50", "decode_step_ms_p50",
                         "prefill_chunk_ms_p50", "kv_walk_useful_share")
    for c in ("sat", "chat")
] + ["admit_wait_p95_ms.chat"]


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_to_read_is_none(metric):
    # an empty window: every span lies outside it
    late = dict(_ctx(), window=(30.0, 40.0))
    assert _read(metric, late) is None
    # the parent's program: the old names, no ids, no serve.step
    old = [
        ("serve.admit", 10.101, 10.102, {"request": "r1"}),
        ("serve.decode_tick", 10.27, 10.271, {"active": 4}),
        ("serve.token_fetch", 10.28, 10.38, {}),
        ("serve.evict", 10.612, 10.613, {"request": "r0",
                                         "status": "completed"}),
    ]
    assert _read(metric, _ctx(old)) is None
    assert _read(metric, _ctx([])) is None


def test_every_new_reader_has_its_entry_and_its_cell():
    b = cells.load_benchmark()
    entries = {m["name"]: m for m in b["per_layer"]}
    for metric in METRICS:
        m = entries[metric]
        sat = metric.endswith(".sat")
        assert m["layer"] == "serving loop" and m["source"] == "program_span"
        assert m["moves"] == ("serve_tokens_per_s" if sat else "itl_p95_ms")
        assert m["workloads"] == [
            "mistral-serve-sat" if sat else "gpt2m-serve-chat-p80"
        ]
    # added at the end of the list, after everything PR 24 brought
    assert [m["name"] for m in b["per_layer"]][-len(METRICS):] == METRICS
