"""Serving resilience: fault injection (serve/faults.py), numeric
guardrails + the degradation ladder, driver-fault isolation (batch bisect),
watchdog, deadlines, shedding, and the HTTP-layer failure surface.

The headline invariant every chaos test here pins: a fault stays contained
to the request it targets — every non-faulted request completes with greedy
output token-identical to a fault-free run of the same engine
configuration."""

import asyncio
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import structures
from repro.models import build_model
from repro.serve import (Engine, EngineConfig, MemoryConfig, Request,
                         ResilienceConfig, SamplingParams, SchedulerConfig,
                         SpeculativeConfig)
from repro.serve import resilience as rsl
from repro.serve.faults import Fault, FaultPlan
from repro.serve.http import Server


def _family_cfgs():
    return {
        "attn": configs.ARCHS["smollm-135m"].reduced(
            vocab=64, d_model=32, n_layers=2, d_ff=64, n_heads=2,
            n_kv_heads=1),
        "mla": configs.ARCHS["deepseek-v3-671b"].reduced(
            vocab=64, d_model=32, n_layers=2),
        "ssd": configs.ARCHS["mamba2-130m"].reduced(
            vocab=64, d_model=32, n_layers=2),
        "rglru": configs.ARCHS["recurrentgemma-2b"].reduced(
            vocab=64, d_model=32, n_layers=4),
    }


def _built(cfg):
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _tiny():
    return _built(_family_cfgs()["attn"])


def _config(res=None, sched=None, **mem):
    return EngineConfig(
        scheduler=sched or SchedulerConfig(slots=2, chunk_size=8),
        memory=MemoryConfig(max_len=64, **mem),
        resilience=res or ResilienceConfig())


def _reqs(n=3, max_new=8):
    prompts = [[4, 5], list(range(6, 15)), [7, 8, 9], [9, 3, 5, 7],
               [11, 12], [13, 14, 15]]
    return [Request(uid=i + 1, prompt=list(p), max_new_tokens=max_new)
            for i, p in enumerate(prompts[:n])]


def _serve(model, params, cfg, reqs):
    eng = Engine(model, params, cfg)
    for r in reqs:
        eng.submit(r)
    eng.run()
    eng.close()
    return eng


def _baseline(model, params, cfg, n=3, max_new=8):
    """Fault-free greedy outputs {uid: tokens} for the same request mix."""
    clean = dataclasses.replace(cfg, resilience=ResilienceConfig())
    reqs = _reqs(n, max_new)
    _serve(model, params, clean, reqs)
    return {r.uid: list(r.output) for r in reqs}


class TestFaultPlan:
    def test_spec_grammar_all_kinds(self):
        plan = FaultPlan.from_spec(
            "nan@6:u3:x2; raise@12:u1:known, slow@20:0.5;drop@2:u4")
        kinds = [f.kind for f in plan.faults]
        assert kinds == ["nan_logits", "driver_error", "slow_step",
                         "drop_conn"]
        nan, rse, slw, drp = plan.faults
        assert (nan.step, nan.uid, nan.count) == (6, 3, 2)
        assert (rse.step, rse.uid, rse.known) == (12, 1, True)
        assert (slw.step, slw.delay_s) == (20, 0.5)
        assert (drp.uid, drp.events) == (4, 2)
        assert plan.faulted_uids() == {3, 1, 4}

    @pytest.mark.parametrize("bad", ["nan@6", "raise@3", "slow@1:u2",
                                     "warp@4:u1", "nan@2:z9"])
    def test_spec_grammar_rejects(self, bad):
        with pytest.raises(ValueError):
            FaultPlan.from_spec(bad)

    def test_seeded_deterministic(self):
        a = FaultPlan.seeded(7, [1, 2, 3])
        b = FaultPlan.seeded(7, [1, 2, 3])
        assert [f.describe() for f in a.faults] == \
               [f.describe() for f in b.faults]
        c = FaultPlan.seeded(8, [1, 2, 3])
        assert [f.describe() for f in a.faults] != \
               [f.describe() for f in c.faults]

    def test_poll_firing_rules(self):
        plan = FaultPlan([Fault("nan_logits", 3, uid=1, count=2),
                          Fault("slow_step", 4, delay_s=0.1),
                          Fault("driver_error", 5, uid=2)])
        assert plan.poll("nan_logits", 2, [1]) == []        # before step
        assert plan.poll("nan_logits", 3, [2]) == []        # uid absent
        assert len(plan.poll("nan_logits", 3, [1, 2])) == 1
        assert len(plan.poll("nan_logits", 4, [1])) == 1    # count=2
        assert plan.poll("nan_logits", 5, [1]) == []        # exhausted
        assert len(plan.poll("slow_step", 9, [1])) == 1
        assert plan.poll("slow_step", 10, [1]) == []        # fires once
        # driver_error persists while its uid keeps being scheduled
        assert len(plan.poll("driver_error", 5, [2])) == 1
        assert len(plan.poll("driver_error", 6, [2])) == 1
        rep = plan.report()
        assert rep["fired"] == 5 and rep["fired_by_kind"] == {
            "nan_logits": 2, "slow_step": 1, "driver_error": 2}


class TestPrimitives:
    def test_row_health_flags_bad_rows_only(self):
        lg = jnp.ones((4, 3, 5))
        lg = lg.at[1, 0, 0].set(jnp.nan)
        lg = lg.at[2, 2, 4].set(jnp.inf)
        assert structures.row_health(lg).tolist() == [True, False, False,
                                                      True]
        lg2 = jnp.ones((3, 5)).at[0, 1].set(2e6)
        assert structures.row_health(lg2, absmax=1e6).tolist() == \
            [False, True, True]
        assert structures.row_health(lg2).tolist() == [True, True, True]

    def test_backoff_deterministic_and_bounded(self):
        a = rsl.Backoff(0.5, 30.0, seed=3)
        b = rsl.Backoff(0.5, 30.0, seed=3)
        da = [a.delay(i) for i in range(8)]
        assert da == [b.delay(i) for i in range(8)]
        for i, d in enumerate(da):
            raw = min(30.0, 0.5 * 2 ** i)
            assert 0.5 * raw <= d < raw
        assert a.delay(40) < 30.0   # capped

    def test_bisect_groups(self):
        assert rsl.bisect_groups([1, 2, 3, 4]) == [[1, 2], [3, 4]]
        assert rsl.bisect_groups([1, 2, 3]) == [[1], [2, 3]]
        assert rsl.bisect_groups([5]) == [[5]]


class TestNumericDegradation:
    def test_nan_trip_recovers_token_identical(self):
        model, params = _tiny()
        base = _baseline(model, params, _config())
        res = ResilienceConfig(fault_spec="nan@4:u2")
        reqs = _reqs()
        eng = _serve(model, params, _config(res=res), reqs)
        assert {r.uid: list(r.output) for r in reqs} == base
        hit = next(r for r in reqs if r.uid == 2)
        assert hit.stop_reason == "length"
        assert hit.degrade_path == ["spec_off"]
        assert eng.stats["numeric_trips"] == 1
        assert eng.stats["requeues"] >= 1
        assert eng.health.snapshot()["numeric_trips"] == 1

    def test_ladder_exhaustion_fails_only_target(self):
        model, params = _tiny()
        base = _baseline(model, params, _config())
        res = ResilienceConfig(fault_spec="nan@4:u2:x3")
        reqs = _reqs()
        eng = _serve(model, params, _config(res=res), reqs)
        hit = next(r for r in reqs if r.uid == 2)
        # rung order is the ladder order: speculation off first, then the
        # activation-quant fallback, then the request alone is failed
        assert hit.degrade_path == ["spec_off", "act_float"]
        assert hit.stop_reason == "numeric_error"
        assert eng.stats["numeric_error_failures"] == 1
        assert eng.stats["degrade_spec_off"] == 1
        assert eng.stats["degrade_act_float"] == 1
        for r in reqs:
            if r.uid != 2:
                assert list(r.output) == base[r.uid]
                assert r.stop_reason == "length"

    @pytest.mark.parametrize("family", ["attn", "mla", "ssd", "rglru"])
    def test_chaos_all_families_paged_spec_int8(self, family):
        """The hard configuration: int8 KV/state cache + paged pool +
        self-speculative decoding, with a NaN fault and a driver fault in
        the same run — non-faulted requests stay token-identical."""
        from repro.quant import QuantConfig
        cfg = dataclasses.replace(_family_cfgs()[family],
                                  quant=QuantConfig(weights="int8",
                                                    cache="int8"))
        model, params = _built(cfg)
        mk = lambda res: EngineConfig(
            scheduler=SchedulerConfig(slots=2, chunk_size=8),
            memory=MemoryConfig(max_len=64, paged=True, page_size=8),
            speculative=SpeculativeConfig(k=3),
            resilience=res)
        base = _baseline(model, params, mk(ResilienceConfig()))
        res = ResilienceConfig(fault_spec="nan@4:u2;raise@8:u3")
        reqs = _reqs()
        eng = _serve(model, params, mk(res), reqs)
        assert eng.fault_plan.report()["fired_by_kind"]["nan_logits"] == 1
        assert eng.stats["step_errors"] >= 1
        for r in reqs:
            if r.uid == 2:
                assert r.stop_reason == "length"   # recovered via ladder
                assert r.output == base[r.uid]     # greedy: still identical
            elif r.uid == 3:
                assert r.stop_reason == "error"
            else:
                assert list(r.output) == base[r.uid]
                assert r.stop_reason == "length"


class TestDriverIsolation:
    def test_unknown_uid_bisected_others_identical(self):
        model, params = _tiny()
        base = _baseline(model, params, _config())
        res = ResilienceConfig(fault_spec="raise@6:u2")
        reqs = _reqs()
        eng = _serve(model, params, _config(res=res), reqs)
        hit = next(r for r in reqs if r.uid == 2)
        assert hit.stop_reason == "error"
        assert eng.stats["step_errors"] >= 2   # fault persisted into probes
        for r in reqs:
            if r.uid != 2:
                assert list(r.output) == base[r.uid]
                assert r.stop_reason == "length"

    def test_known_uid_skips_bisect(self):
        model, params = _tiny()
        base = _baseline(model, params, _config())
        res = ResilienceConfig(fault_spec="raise@6:u2:known")
        reqs = _reqs()
        eng = _serve(model, params, _config(res=res), reqs)
        hit = next(r for r in reqs if r.uid == 2)
        assert hit.stop_reason == "error"
        # the exception named its uid: exactly one failing step, no probe
        assert eng.stats["step_errors"] == 1
        for r in reqs:
            if r.uid != 2:
                assert list(r.output) == base[r.uid]


class TestWatchdogDeadlinesShedding:
    def test_watchdog_trips_without_wedging(self):
        model, params = _tiny()
        res = ResilienceConfig(fault_spec="slow@3:0.4",
                               watchdog_deadline_s=0.15)
        reqs = _reqs()
        eng = _serve(model, params, _config(res=res), reqs)
        snap = eng.health.snapshot()
        assert snap["watchdog_trips"] >= 1
        assert all(r.stop_reason == "length" for r in reqs)
        assert eng._watchdog is None   # close() stopped the thread

    def test_request_deadline_expires(self):
        model, params = _tiny()
        reqs = _reqs()
        reqs[1].deadline_s = 0.0   # already expired at first tick
        eng = _serve(model, params, _config(), reqs)
        assert reqs[1].stop_reason == "deadline"
        assert reqs[1].t_done is not None
        assert all(r.stop_reason == "length" for r in reqs
                   if r.uid != reqs[1].uid)
        assert eng.stats["deadline_expired"] == 1

    def test_shed_above_high_water(self):
        model, params = _tiny()
        res = ResilienceConfig(queue_high_water=3)
        reqs = _reqs(6)
        eng = _serve(model, params, _config(res=res), reqs)
        shed = [r for r in reqs if r.stop_reason == "shed"]
        kept = [r for r in reqs if r.stop_reason == "length"]
        assert len(shed) == 3 and len(kept) == 3
        assert eng.stats["shed"] == 3
        # newest-first shedding: the first-submitted requests survive
        assert {r.uid for r in kept} == {1, 2, 3}
        assert eng.overloaded() is False

    def test_sla_report_nulls_for_empty_class(self):
        model, params = _tiny()
        res = ResilienceConfig(queue_high_water=0)
        reqs = _reqs(2)
        eng = _serve(model, params, _config(res=res), reqs)
        assert all(r.stop_reason == "shed" for r in reqs)
        c0 = eng.sla_report()["classes"]["0"]
        assert c0["requests"] == 2 and c0["completed"] == 0
        assert c0["stop_reasons"] == {"shed": 2}
        # explicit nulls, never a fabricated 0.0 latency
        assert c0["ttft_p50_s"] is None and c0["tpot_p99_s"] is None

    def test_healthz_and_resilience_report(self):
        model, params = _tiny()
        res = ResilienceConfig(fault_spec="nan@4:u2")
        reqs = _reqs()
        eng = _serve(model, params, _config(res=res, paged=True,
                                            page_size=8), reqs)
        hz = eng.healthz()
        assert hz["state"] in ("ok", "degraded")
        assert hz["queue_depth"] == 0 and hz["active"] == 0
        assert hz["slots"] == 2 and hz["overloaded"] is False
        assert "occupancy" in hz
        rep = eng.resilience_report()
        assert rep["numeric_trips"] == 1
        assert rep["faults"]["fired"] == 1


async def _raw(port, method, path, body=b""):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    req = f"{method} {path} HTTP/1.1\r\nHost: t\r\n".encode()
    req += b"Content-Length: %d\r\n\r\n%s" % (len(body), body)
    writer.write(req)
    await writer.drain()
    raw = await reader.read()
    writer.close()
    head, _, payload = raw.partition(b"\r\n\r\n")
    lines = head.decode().split("\r\n")
    status = int(lines[0].split(" ")[1])
    headers = {}
    for ln in lines[1:]:
        k, _, v = ln.partition(":")
        headers[k.strip().lower()] = v.strip()
    return status, headers, payload


class TestHTTPResilience:
    def test_structured_errors_and_healthz(self):
        model, params = _tiny()
        eng = Engine(model, params, _config())

        async def run():
            srv = Server(eng, port=0)
            port = await srv.start()
            bad = await _raw(port, "POST", "/v1/generate",
                             b'{"prompt": "oops"}')
            missing = await _raw(port, "POST", "/v1/generate", b"{}")
            nf = await _raw(port, "GET", "/nope")
            hz = await _raw(port, "GET", "/healthz")
            await srv.stop()
            return bad, missing, nf, hz

        bad, missing, nf, hz = asyncio.run(run())
        assert bad[0] == 400
        assert json.loads(bad[2])["error"]["reason"].startswith("prompt:")
        assert missing[0] == 400
        assert "missing" in json.loads(missing[2])["error"]["reason"]
        assert nf[0] == 404
        assert json.loads(nf[2])["error"]["type"] == "not_found"
        assert hz[0] == 200
        assert json.loads(hz[2])["state"] == "ok"
        eng.close()

    def test_overloaded_429_with_retry_after(self):
        model, params = _tiny()
        eng = Engine(model, params, _config(
            res=ResilienceConfig(queue_high_water=0)))

        async def run():
            srv = Server(eng, port=0)
            port = await srv.start()
            r1 = await _raw(port, "POST", "/v1/generate",
                            b'{"prompt": [4, 5]}')
            r2 = await _raw(port, "POST", "/v1/generate",
                            b'{"prompt": [4, 5]}')
            await srv.stop()
            return r1, r2

        r1, r2 = asyncio.run(run())
        assert r1[0] == 429 and r2[0] == 429
        assert json.loads(r1[2])["error"]["type"] == "overloaded"
        assert int(r1[1]["retry-after"]) >= 1
        # the shared backoff advances across consecutive rejections
        assert int(r2[1]["retry-after"]) >= int(r1[1]["retry-after"])
        eng.close()

    def test_draining_503(self):
        model, params = _tiny()
        eng = Engine(model, params, _config())
        eng.health.drain()

        async def run():
            srv = Server(eng, port=0)
            port = await srv.start()
            gen = await _raw(port, "POST", "/v1/generate",
                             b'{"prompt": [4, 5]}')
            hz = await _raw(port, "GET", "/healthz")
            await srv.stop()
            return gen, hz

        gen, hz = asyncio.run(run())
        assert gen[0] == 503 and "retry-after" in gen[1]
        assert json.loads(gen[2])["error"]["type"] == "draining"
        assert hz[0] == 503 and "retry-after" in hz[1]
        eng.close()

    def test_sse_heartbeat_between_tokens(self):
        model, params = _tiny()
        # a 0.6 s stall with a 0.05 s heartbeat: the stream must carry SSE
        # comment lines while the engine is stuck, and still deliver every
        # token afterwards
        eng = Engine(model, params, _config(
            res=ResilienceConfig(fault_spec="slow@2:0.6",
                                 heartbeat_s=0.05)))
        ref = Engine(model, params, _config()).generate_batch(
            [[4, 5, 6]], SamplingParams(max_new_tokens=5))[0].output

        async def run():
            srv = Server(eng, port=0)
            port = await srv.start()
            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           port)
            body = b'{"prompt": [4, 5, 6], "max_new_tokens": 5}'
            writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                         b"Content-Length: %d\r\n\r\n%s"
                         % (len(body), body))
            await writer.drain()
            events, heartbeats = [], 0
            while True:
                line = await asyncio.wait_for(reader.readline(), timeout=60)
                if not line:
                    break
                if line.startswith(b": hb"):
                    heartbeats += 1
                elif line.startswith(b"data: "):
                    events.append(json.loads(line[6:]))
                    if events[-1].get("done"):
                        break
            writer.close()
            await srv.stop()
            return events, heartbeats

        events, heartbeats = asyncio.run(run())
        assert heartbeats >= 1
        assert [e["token"] for e in events[:-1]] == ref
        assert events[-1]["done"] and events[-1]["stop_reason"] == "length"
        eng.close()


class TestLauncherExitCode:
    """``launch/serve.py`` must not report a failed run as finished: with
    no fault plan armed, an errored request or a recorded step error is a
    non-zero exit; an armed plan fails requests on purpose."""

    _ARGV = ["--arch", "smollm-135m", "--reduced", "--requests", "2",
             "--max-new", "2", "--chunk", "8", "--max-len", "32"]

    def test_clean_run_has_no_failures(self):
        from repro.launch import serve
        model, params = _tiny()
        reqs = _reqs(2, 4)
        eng = _serve(model, params, _config(), reqs)
        assert serve.failures(eng, reqs) == []

    def test_unplanned_step_error_is_a_failure(self):
        from repro.launch import serve
        model, params = _tiny()
        eng = Engine(model, params, _config())

        def refused(*a):
            raise RuntimeError("the device refused the step")
        eng._step = refused
        reqs = _reqs(2, 4)
        for r in reqs:
            eng.submit(r)
        eng.run()
        eng.close()
        assert {r.stop_reason for r in reqs} == {"error"}
        bad = serve.failures(eng, reqs)
        assert any("ended in error" in b for b in bad)
        assert any("step errors" in b for b in bad)

    def test_armed_fault_plan_is_not_a_failure(self):
        from repro.launch import serve
        model, params = _tiny()
        cfg = _config(res=ResilienceConfig(fault_spec="raise@2:u1"))
        reqs = _reqs(3, 4)
        eng = _serve(model, params, cfg, reqs)
        assert eng.stats["step_errors"] >= 1
        assert serve.failures(eng, reqs) == []

    def test_main_exits_nonzero_on_step_errors(self, monkeypatch):
        from repro.launch import serve
        from repro.serve import engine as engine_mod

        def refused(self, finished):
            raise RuntimeError("the device refused the step")
        monkeypatch.setattr(engine_mod.Engine, "_advance", refused)
        with pytest.raises(SystemExit) as exc:
            serve.main(self._ARGV)
        assert "FAILED" in str(exc.value.code)
