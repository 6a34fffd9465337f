"""The program's trace surface: every dispatched program has a stable name
(the trace names device operations ``<program>/<op>``), and the serve loop
writes its ``serve.*`` spans (``repro.tracing.span``)."""

import glob
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import models as M
from repro.core.jobs import PAPER_JOBS
from repro.core.offload import OffloadRuntime
from repro.serve import ServeConfig, ServeEngine

LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
OLD_NAMES = {"jit_step", "jit__lambda", "jit_ins", "jit_chunk_fn",
             "jit_program"}


class lowered_modules:
    """The names of the modules lowered inside the ``with`` block."""

    def __enter__(self):
        self.names = set()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self.names

    def _on(self, event, duration, fun_name=None, **_kw):
        if event == LOWERED:
            # "jit(serve_step)" is the module jit_serve_step
            self.names.add(fun_name.replace("(", "_").rstrip(")"))

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


@pytest.mark.parametrize("fuse", [None, 2])
@pytest.mark.parametrize("kind", sorted(PAPER_JOBS))
def test_offload_programs_are_named_by_job(kind, fuse):
    rt = OffloadRuntime(jax.devices()[:1])
    text = rt.lowered_text(PAPER_JOBS[kind](), n=1, fuse=fuse)
    want = f"jit_offload_{kind}" + ("_fused" if fuse else "")
    assert text.startswith(f"HloModule {want},")


def _engine(mode: str = "step") -> ServeEngine:
    cfg = M.reduced(M.get("smollm-360m"))
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    params = M.init_params(jax.random.key(0), cfg)
    return ServeEngine(cfg, params, mesh, ServeConfig(
        batch=2, max_len=32, prefill_bucket=8, decode_mode=mode))


def _requests(vocab: int) -> list:
    gen = np.random.default_rng(0)
    return [(gen.integers(0, vocab, n).astype(np.int32), m)
            for n, m in ((5, 3), (9, 2), (3, 2))]


SERVE_PATHS = {
    "many": {"jit_serve_prefill", "jit_serve_insert",
             "jit_serve_decode_step"},
    "step": {"jit_serve_first_token", "jit_serve_sample_step"},
    "chunk": {"jit_serve_first_token", "jit_serve_decode_chunk"},
    "host": {"jit_serve_step"},
}


@pytest.mark.parametrize("path", sorted(SERVE_PATHS))
def test_serve_programs_are_named(path):
    eng = _engine("step" if path == "many" else path)
    with lowered_modules() as names:
        if path == "many":
            eng.generate_many(_requests(eng.cfg.vocab_size))
        else:
            prompts = np.ones((2, 5), np.int32)
            eng.generate(prompts, 10)
    assert SERVE_PATHS[path] <= names
    assert not names & OLD_NAMES


def _host_spans(logdir: str) -> list:
    """[(start_ns, end_ns, name, stats)] of the ``serve.*`` host events."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("serve."):
                        out.append((ev.start_ns,
                                    ev.start_ns + ev.duration_ns, ev.name,
                                    {k: v for k, v in ev.stats}))
    return sorted(out, key=lambda sp: sp[:2])


def test_generate_many_writes_serve_spans(tmp_path):
    eng = _engine()
    reqs = _requests(eng.cfg.vocab_size)
    eng.generate_many(reqs)                       # compiles outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.generate_many(reqs)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    names = [n for _, _, n, _ in spans]
    # 3 requests into 2 slots: the third waits for a retire; the longest
    # (3 tokens) and the refill (2 tokens, admitted after step 2) take 4
    # decode steps, each followed by its retire pass
    assert names.count("serve.admit") == len(reqs)
    assert names.count("serve.decode") == names.count("serve.retire") == 4
    assert names.count("serve.drain") == 1
    assert names[-1] == "serve.drain"
    slots = [m["slot"] for _, _, n, m in spans if n == "serve.admit"]
    assert slots == [0, 1, 1]
    # each step's live slots attend position + 1 cells: prompts of 5 and 9
    # at positions 4 and 8, then 5 and 9; the refill (3) at 2 beside the
    # first at 6; the refill alone at 3
    kv = [m["kv_positions"] for _, _, n, m in spans if n == "serve.decode"]
    assert kv == [5 + 9, 6 + 10, 7 + 3, 4]
    # the kernel copies whole chunks: a 32-position cache is one chunk
    read = [m["kv_read_positions"] for _, _, n, m in spans
            if n == "serve.decode"]
    assert read == [2 * 32, 2 * 32, 2 * 32, 32]
