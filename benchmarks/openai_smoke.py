"""OpenAI front-door smoke: a 2-replica routed fleet behind
``accelerate-tpu route --http``, driven by an OpenAI client (the real
``openai`` package when installed, a byte-identical stdlib fallback
otherwise — the wire contract is what's under test, not the SDK).

Asserts, over a mixed greedy/sampled/schema-constrained trace:

1. every non-stream completion/chat answer is well-formed (object, id
   prefix, usage arithmetic) and a fixed ``seed`` reproduces byte-equal
   text through the router;
2. every ``response_format: json_schema`` answer parses as JSON AND
   validates against the schema;
3. SSE streams frame correctly end to end — every stream yields exactly
   one finish chunk (with usage) and one ``data: [DONE]`` terminator,
   and a ``stop`` sequence never over-sends past the truncation;
4. OpenAI error objects come back for malformed requests (the fleet
   answers 400s, it does not die);
5. each replica still reports ``decode_compiles == 1`` after the whole
   trace — per-request sampling/grammar rides the ONE compiled decode
   executable.

Run directly (``make openai-smoke``). No absolute wall-clock gates
(timing-noise rule).
"""

import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the parent drives HTTP only — replicas are their own jax processes
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ENGINE_ARGS = [
    "--preset", "tiny", "--num-slots", "4", "--block-size", "8",
    "--max-seq-len", "96", "--prefill-chunk", "8", "--decode-burst", "2",
    "--max-new-tokens", "16", "--logprobs-topn", "2",
]

SCHEMA = {
    "type": "object",
    "properties": {
        "name": {"enum": ["alpha", "beta", "gamma"]},
        "n": {"type": "integer"},
    },
    "required": ["name", "n"],
}


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _cli_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""  # single-device replicas
    env.pop("ACCELERATE_TELEMETRY", None)
    return env


def _wait_ready(port, proc, timeout=300):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(f"route exited rc={proc.returncode}")
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=2
            ) as r:
                if json.loads(r.read()).get("state") == "ready":
                    return
        except (OSError, ValueError):
            pass
        time.sleep(0.25)
    raise RuntimeError("route fleet never became ready")


class _StdlibClient:
    """Just enough of the OpenAI HTTP contract to stand in for the SDK:
    POST JSON, surface the error object, iterate SSE data: lines."""

    name = "stdlib"

    def __init__(self, base_url):
        self.base_url = base_url.rstrip("/")

    def _post(self, path, body, stream=False):
        req = urllib.request.Request(
            self.base_url + path, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        try:
            resp = urllib.request.urlopen(req, timeout=300)
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())
        with resp:
            raw = resp.read().decode()
        return resp.status, raw if stream else json.loads(raw)

    def completion(self, **body):
        return self._post("/completions", body)

    def chat(self, **body):
        return self._post("/chat/completions", body)

    def chat_stream(self, **body):
        status, raw = self._post(
            "/chat/completions", dict(body, stream=True), stream=True
        )
        assert status == 200, raw
        events = [
            line[6:] for line in raw.split("\n\n") if line.startswith("data: ")
        ]
        assert events and events[-1] == "[DONE]", "missing [DONE] terminator"
        return [json.loads(e) for e in events[:-1]]


class _OpenAIClient(_StdlibClient):
    """The real SDK for the happy paths; error-path probes stay on the
    stdlib POST so the raw error object remains inspectable."""

    name = "openai"

    def __init__(self, base_url, openai_module):
        super().__init__(base_url)
        self._sdk = openai_module.OpenAI(base_url=base_url, api_key="smoke")

    def chat(self, **body):
        out = self._sdk.chat.completions.create(
            model=body.pop("model", "accelerate-tpu"), **body
        )
        return 200, out.model_dump()

    def chat_stream(self, **body):
        stream = self._sdk.chat.completions.create(
            model=body.pop("model", "accelerate-tpu"), stream=True, **body
        )
        return [chunk.model_dump() for chunk in stream]


def _make_client(base_url):
    try:
        import openai  # noqa: F401 — optional, never installed by us
    except ImportError:
        return _StdlibClient(base_url)
    return _OpenAIClient(base_url, openai)


def _check_stream(chunks):
    """Exactly-once framing: one finish chunk, usage on it, text joins."""
    finals = [c for c in chunks if c["choices"][0].get("finish_reason")]
    assert len(finals) == 1, f"{len(finals)} finish chunks in one stream"
    assert finals[0].get("usage"), "finish chunk must carry usage"
    text = "".join(
        c["choices"][0].get("delta", {}).get("content") or "" for c in chunks
    )
    return text, finals[0]


def run(platform: str = "cpu", n_requests: int = 12) -> dict:
    result: dict = {"n_requests": n_requests}
    port = _free_port()
    with tempfile.TemporaryDirectory() as logdir:
        proc = subprocess.Popen(
            [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
             "route", "--replicas", "2", "--logging-dir", logdir,
             "--http", str(port), *ENGINE_ARGS],
            env=_cli_env(), stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True,
        )
        try:
            _wait_ready(port, proc)
            client = _make_client(f"http://127.0.0.1:{port}/v1")
            result["client"] = client.name

            # -- mixed non-stream trace ---------------------------------
            schema_ok = 0
            for i in range(n_requests):
                kind = i % 3
                if kind == 0:  # greedy completion
                    st, body = client.completion(
                        prompt=f"request {i}", temperature=0, max_tokens=8,
                    )
                    assert st == 200, body
                    assert body["object"] == "text_completion"
                    u = body["usage"]
                    assert u["total_tokens"] == (
                        u["prompt_tokens"] + u["completion_tokens"]
                    )
                elif kind == 1:  # sampled chat with a fixed seed
                    st, body = client.chat(
                        messages=[{"role": "user", "content": f"hello {i}"}],
                        temperature=0.8, seed=1000 + i, max_tokens=8,
                    )
                    assert st == 200, body
                    assert body["choices"][0]["message"]["role"] == "assistant"
                else:  # schema-constrained chat
                    st, body = client.chat(
                        messages=[{"role": "user", "content": "json please"}],
                        temperature=0.7, seed=i, max_tokens=48,
                        response_format={
                            "type": "json_schema",
                            "json_schema": {"name": "t", "schema": SCHEMA},
                        },
                    )
                    assert st == 200, body
                    value = json.loads(body["choices"][0]["message"]["content"])
                    assert value["name"] in SCHEMA["properties"]["name"]["enum"]
                    assert isinstance(value["n"], int)
                    assert set(SCHEMA["required"]) <= set(value)
                    schema_ok += 1
            result["schema_valid"] = schema_ok

            # seed determinism THROUGH the router (either replica)
            req = dict(
                messages=[{"role": "user", "content": "det"}],
                temperature=0.9, seed=7, max_tokens=8,
            )
            _, a = client.chat(**req)
            _, b = client.chat(**req)
            assert (
                a["choices"][0]["message"]["content"]
                == b["choices"][0]["message"]["content"]
            ), "fixed seed must reproduce through the fleet"
            result["seed_deterministic"] = True

            # -- streaming legs -----------------------------------------
            streams = 0
            for i in range(4):
                chunks = client.chat_stream(
                    messages=[{"role": "user", "content": f"stream {i}"}],
                    temperature=0 if i % 2 else 0.8, seed=i, max_tokens=8,
                )
                text, final = _check_stream(chunks)
                assert len(text) >= 1
                streams += 1
            # stop sequences: the stream never over-sends past truncation
            chunks = client.chat_stream(
                messages=[{"role": "user", "content": "stop test"}],
                temperature=0, max_tokens=12, stop=["X"],
            )
            text, final = _check_stream(chunks)
            assert len(text) == final["usage"]["completion_tokens"], (
                "streamed more text than the stop-truncated answer"
            )
            result["streams_exactly_once"] = streams + 1

            # -- error objects (raw POST, SDK-independent) --------------
            raw = _StdlibClient(f"http://127.0.0.1:{port}/v1")
            st, body = raw.completion(prompt="x", n=3)
            assert st == 400 and body["error"]["param"] == "n", body
            st, body = raw.completion(prompt=42)
            assert st == 400 and body["error"]["type"] == "invalid_request_error"
            st, body = raw.chat(messages=[])
            assert st == 400 and body["error"]["param"] == "messages"
            result["error_objects"] = 3

            # -- one executable per replica -----------------------------
            trail = os.path.join(logdir, "router", "replicas.jsonl")
            base_urls = set()
            with open(trail) as f:
                for line in f:
                    try:
                        row = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if row.get("base_url"):
                        base_urls.add(row["base_url"])
            assert len(base_urls) == 2, f"expected 2 replicas: {base_urls}"
            compiles, sampled, masked = [], 0, 0
            for url in sorted(base_urls):
                with urllib.request.urlopen(url + "/stats", timeout=10) as r:
                    stats = json.loads(r.read())
                compiles.append(stats["decode_compiles"])
                sampled += stats.get("sampled_tokens_sample", 0)
                masked += stats.get("grammar_masked_steps", 0)
            assert compiles == [1, 1], (
                f"per-request sampling/grammar recompiled a replica: {compiles}"
            )
            assert sampled > 0, "the sampled lanes never fired"
            assert masked > 0, "the grammar mask never fired"
            result["decode_compiles"] = compiles
            result["sampled_tokens"] = sampled
            result["grammar_masked_steps"] = masked

            proc.stdin.close()  # EOF → drain → exit 0
            rc = proc.wait(timeout=180)
            assert rc == 0, f"route drain exited rc={rc}"
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
    return result


def main():
    r = run()
    print(
        f"openai-smoke: client={r['client']} n={r['n_requests']} "
        f"schema_valid={r['schema_valid']} "
        f"streams={r['streams_exactly_once']} "
        f"decode_compiles={r['decode_compiles']} "
        f"sampled_tokens={r['sampled_tokens']} "
        f"grammar_masked_steps={r['grammar_masked_steps']}"
    )
    print(
        "OPENAI SMOKE OK: 2-replica fleet, OpenAI contract end to end, "
        "schema-valid constrained output, exactly-once SSE, one decode "
        "executable per replica"
    )


if __name__ == "__main__":
    main()
