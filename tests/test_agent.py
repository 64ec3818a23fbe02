"""Agent-loop tests: provider contract, tool rounds, task executor, CLI.

Mirrors the reference's mock-LLM pattern (fei/tests/test_litellm.py:51-110):
the MockProvider plays the role of the patched litellm_completion.
"""

import asyncio
import json

import pytest

from fei_tpu.agent import (
    Assistant,
    ConversationManager,
    MockProvider,
    ProviderResponse,
    TaskExecutor,
    ToolCall,
)
from fei_tpu.agent.providers import (
    extract_tool_calls,
    render_tool_prompt,
    stream_visible,
)
from fei_tpu.tools import ToolRegistry, create_code_tools


def make_assistant(script, registry=None):
    provider = MockProvider(script)
    return Assistant(provider=provider, tool_registry=registry), provider


class TestExtractToolCalls:
    def test_extracts_and_strips(self):
        text = 'Let me look.\n<tool_call>{"name": "GlobTool", "arguments": {"pattern": "*.py"}}</tool_call>'
        content, calls = extract_tool_calls(text)
        assert content == "Let me look."
        assert calls[0].name == "GlobTool"
        assert calls[0].arguments == {"pattern": "*.py"}

    def test_multiple_calls(self):
        text = (
            '<tool_call>{"name": "A", "arguments": {}}</tool_call>'
            '<tool_call>{"name": "B", "arguments": {"x": 1}}</tool_call>'
        )
        _, calls = extract_tool_calls(text)
        assert [c.name for c in calls] == ["A", "B"]

    def test_malformed_json_ignored(self):
        content, calls = extract_tool_calls("<tool_call>{not json}</tool_call>ok")
        assert calls == [] and content == "ok"

    def test_stream_visible_holds_partial_tag(self):
        assert stream_visible("Sure. <tool_ca") == "Sure. "
        assert stream_visible("Sure. <tool_cat") == "Sure. <tool_cat"

    def test_stream_visible_strips_block_keeps_tail(self):
        text = 'before <tool_call>{"name":"A","arguments":{}}</tool_call> after'
        assert stream_visible(text) == "before  after"
        # open block held back entirely
        assert stream_visible('x <tool_call>{"name"') == "x "

    def test_stream_visible_monotonic(self):
        full = 'hi <tool_call>{"name":"A","arguments":{}}</tool_call> bye'
        prev = ""
        for i in range(len(full) + 1):
            vis = stream_visible(full[:i])
            assert vis.startswith(prev)
            prev = vis

    def test_tool_prompt_lists_tools(self):
        reg = ToolRegistry()
        create_code_tools(reg)
        prompt = render_tool_prompt(reg.get_schemas())
        assert "GlobTool" in prompt and "<tool_call>" in prompt


class TestAssistantLoop:
    def test_plain_chat(self):
        assistant, provider = make_assistant([ProviderResponse("hello there")])
        out = asyncio.run(assistant.chat("hi"))
        assert out == "hello there"
        roles = [m["role"] for m in assistant.conversation.messages]
        assert roles == ["user", "assistant"]

    def test_tool_round_trip(self, tmp_path):
        (tmp_path / "a.py").write_text("x = 1\n")
        reg = ToolRegistry()
        create_code_tools(reg)
        script = [
            f'<tool_call>{{"name": "GlobTool", "arguments": {{"pattern": "*.py", "path": "{tmp_path}"}}}}</tool_call>',
            ProviderResponse("I found one python file."),
        ]
        assistant, provider = make_assistant(script, reg)
        out = asyncio.run(assistant.chat("what python files are there?"))
        assert out == "I found one python file."
        # second provider call must carry the tool result message
        second = provider.calls[1]["messages"]
        tool_msgs = [m for m in second if m["role"] == "tool"]
        assert len(tool_msgs) == 1
        assert "a.py" in tool_msgs[0]["content"]

    def test_tool_error_fed_back(self):
        reg = ToolRegistry()
        create_code_tools(reg)
        script = [
            '<tool_call>{"name": "View", "arguments": {"file_path": "/definitely/missing"}}</tool_call>',
            ProviderResponse("the file is missing"),
        ]
        assistant, provider = make_assistant(script, reg)
        out = asyncio.run(assistant.chat("read it"))
        assert out == "the file is missing"
        tool_msg = [m for m in provider.calls[1]["messages"] if m["role"] == "tool"][0]
        assert "error" in tool_msg["content"]

    def test_round_limit(self):
        reg = ToolRegistry()
        reg.register_tool("Loop", "loops", {"type": "object", "properties": {}},
                          lambda: {"ok": True})
        looping = '<tool_call>{"name": "Loop", "arguments": {}}</tool_call>'
        assistant, provider = make_assistant([looping] * 20, reg)
        assistant.max_tool_rounds = 3
        asyncio.run(assistant.chat("go"))
        assert len(provider.calls) == 4  # initial + 3 rounds

    def test_empty_response_salvaged_from_tool_output(self):
        reg = ToolRegistry()
        reg.register_tool("Info", "info", {"type": "object", "properties": {}},
                          lambda: {"data": 42})
        script = [
            '<tool_call>{"name": "Info", "arguments": {}}</tool_call>',
            ProviderResponse(""),
        ]
        assistant, _ = make_assistant(script, reg)
        out = asyncio.run(assistant.chat("info please"))
        assert "42" in out

    def test_streaming_callback(self):
        deltas = []
        assistant, _ = make_assistant([ProviderResponse("streamed reply")])
        assistant.on_text = deltas.append
        out = asyncio.run(assistant.chat("hi"))
        assert out == "streamed reply"
        assert "".join(deltas) == "streamed reply"


class TestConversationManager:
    def test_tool_results_stringified(self):
        conv = ConversationManager()
        call = ToolCall("id1", "T", {})
        conv.add_tool_results([(call, {"a": 1})])
        assert json.loads(conv.messages[0]["content"]) == {"a": 1}

    def test_trim_respects_budget_and_pairs(self):
        conv = ConversationManager(max_context_tokens=50)
        conv.add_user_message("word " * 100)
        conv.add_assistant_message("reply", [ToolCall("i", "T", {})])
        conv.add_tool_results([(ToolCall("i", "T", {}), "out")])
        conv.add_user_message("latest question")
        conv.add_assistant_message("latest answer")
        roles = [m["role"] for m in conv.messages]
        assert "tool" not in roles or roles.index("tool") != 0  # never orphaned
        assert conv.token_estimate() <= 50 or len(conv.messages) == 2


class TestTaskExecutor:
    def test_completes_on_signal(self):
        script = [
            ProviderResponse("step one done"),
            ProviderResponse("all finished [TASK_COMPLETE]"),
        ]
        assistant, provider = make_assistant(script)
        ctx = asyncio.run(TaskExecutor(assistant, max_iterations=5).execute_task("do it"))
        assert ctx.completed and ctx.iterations == 2
        assert ctx.final_response == "all finished"
        # first prompt wraps task in the protocol scaffold
        assert "[TASK_COMPLETE]" in provider.calls[0]["messages"][0]["content"]

    def test_iteration_cap(self):
        assistant, _ = make_assistant([ProviderResponse("still going")] * 10)
        ctx = asyncio.run(TaskExecutor(assistant, max_iterations=3).execute_task("loop"))
        assert not ctx.completed and ctx.iterations == 3

    def test_interactive_stop(self):
        assistant, _ = make_assistant([ProviderResponse("going")] * 10)
        ctx = asyncio.run(
            TaskExecutor(assistant, max_iterations=10).execute_interactive(
                "t", confirm=lambda ctx, resp: ctx.iterations < 2
            )
        )
        assert ctx.iterations == 2


class TestJaxLocalProvider:
    def test_end_to_end_tiny_engine(self):
        import jax.numpy as jnp

        from fei_tpu.agent.providers import JaxLocalProvider
        from fei_tpu.engine import InferenceEngine

        engine = InferenceEngine.from_config(
            "tiny", dtype=jnp.float32, max_seq_len=512, tokenizer="byte"
        )
        provider = JaxLocalProvider(engine=engine, gen_overrides={"ignore_eos": True})
        resp = provider.complete(
            [{"role": "user", "content": "hello"}], system="be brief", max_tokens=8
        )
        assert isinstance(resp.content, str)
        assert resp.usage["completion_tokens"] == 8

    def test_stream_detok_byte_identical(self, monkeypatch):
        """The stream loop detokenizes incrementally (bounded pending
        window + cached context decode) instead of re-decoding the whole
        sequence per token; the streamed text must stay byte-identical
        to a from-scratch decode of every emitted token id."""
        import jax.numpy as jnp

        from fei_tpu.agent.providers import (
            JaxLocalProvider,
            extract_tool_calls,
            stream_visible,
        )
        from fei_tpu.engine import InferenceEngine

        engine = InferenceEngine.from_config(
            "tiny", dtype=jnp.float32, max_seq_len=512, tokenizer="byte"
        )
        provider = JaxLocalProvider(engine=engine, gen_overrides={"ignore_eos": True})
        captured: list[int] = []
        real = engine.generate_stream

        def spy(ids, gen, **kw):
            for t in real(ids, gen, **kw):
                captured.append(t)
                yield t

        monkeypatch.setattr(engine, "generate_stream", spy)
        # byte tokenizer + a random tiny model: the stream crosses plenty of
        # invalid / partial UTF-8 boundaries, the hard case for folding
        gen = provider.stream(
            [{"role": "user", "content": "héllo ✓ bytes"}], max_tokens=48
        )
        chunks = []
        while True:
            try:
                chunks.append(next(gen))
            except StopIteration as fin:
                resp = fin.value
                break
        assert len(captured) == 48
        full = engine.tokenizer.decode(captured)
        assert "".join(chunks) == stream_visible(full, provider.tool_trigger)
        content, _ = extract_tool_calls(full, provider.tool_trigger)
        assert resp.content == content
        assert resp.usage["completion_tokens"] == 48

    def test_assistant_over_local_engine(self):
        import jax.numpy as jnp

        from fei_tpu.agent.providers import JaxLocalProvider
        from fei_tpu.engine import InferenceEngine

        engine = InferenceEngine.from_config(
            "tiny", dtype=jnp.float32, max_seq_len=512, tokenizer="byte"
        )
        provider = JaxLocalProvider(engine=engine, gen_overrides={"ignore_eos": True})
        assistant = Assistant(provider=provider, max_tokens=8)
        out = asyncio.run(assistant.chat("2+2?"))
        assert isinstance(out, str)


class TestCLI:
    def test_one_shot_mock(self, capsys, tmp_path, monkeypatch):
        import fei_tpu.ui.cli as cli

        monkeypatch.setattr(cli, "HISTORY_FILE", str(tmp_path / "history.json"))
        rc = cli.main(["--provider", "mock", "--no-stream", "--message", "ping"])
        assert rc == 0
        assert "[mock] echo: ping" in capsys.readouterr().out

    def test_history_subcommand(self, capsys, tmp_path, monkeypatch):
        import fei_tpu.ui.cli as cli

        monkeypatch.setattr(cli, "HISTORY_FILE", str(tmp_path / "history.json"))
        cli.main(["--provider", "mock", "--no-stream", "--message", "remember me"])
        rc = cli.main(["history", "list"])
        out = capsys.readouterr().out
        assert rc == 0 and "remember me" in out


class TestAskAndSearch:
    """fei ask / fei search (parity: ref fei/ui/cli.py:572-728, without the
    reference's hardcoded fallback API key)."""

    _RESULTS = {
        "web": {
            "results": [
                {"title": "JAX docs", "url": "https://jax.dev",
                 "description": "Composable transforms."},
                {"title": "Pallas guide", "url": "https://jax.dev/pallas",
                 "description": "TPU kernels."},
            ]
        }
    }

    def test_search_subcommand(self, capsys, monkeypatch):
        import fei_tpu.ui.cli as cli

        monkeypatch.setattr(
            cli, "run_search",
            lambda q, count=5, manager=None: cli._extract_search_results(
                self._RESULTS
            ),
        )
        rc = cli.main(["search", "jax"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "JAX docs" in out and "https://jax.dev" in out

    def test_search_failure_is_readable(self, capsys, monkeypatch):
        import fei_tpu.ui.cli as cli

        def boom(q, count=5, manager=None):
            raise RuntimeError("no brave key configured")

        monkeypatch.setattr(cli, "run_search", boom)
        rc = cli.main(["search", "jax"])
        assert rc == 1
        assert "no brave key" in capsys.readouterr().err

    def test_ask_stuffs_results_into_prompt(self, capsys, tmp_path, monkeypatch):
        import fei_tpu.ui.cli as cli

        monkeypatch.setattr(cli, "HISTORY_FILE", str(tmp_path / "h.json"))
        monkeypatch.setattr(
            cli, "run_search",
            lambda q, count=5, manager=None: cli._extract_search_results(
                self._RESULTS
            ),
        )
        rc = cli.main(
            ["--provider", "mock", "--no-stream", "ask", "what is jax?"]
        )
        out = capsys.readouterr().out
        assert rc == 0
        # MockProvider echoes a (truncated) prefix of its prompt back: the
        # stuffed-search preamble must have reached the model
        assert "Answer the question using the web search results" in out
        assert "Search results for: what is" in out
        # and the ask landed in history
        hist = cli.History(str(tmp_path / "h.json"))
        assert any(e["prompt"].startswith("[ask]") for e in hist.entries)

    def test_ask_no_search(self, capsys, tmp_path, monkeypatch):
        import fei_tpu.ui.cli as cli

        monkeypatch.setattr(cli, "HISTORY_FILE", str(tmp_path / "h.json"))

        def never(*a, **k):
            raise AssertionError("search must not run with --no-search")

        monkeypatch.setattr(cli, "run_search", never)
        rc = cli.main(
            ["--provider", "mock", "--no-stream", "ask", "--no-search", "2+2?"]
        )
        assert rc == 0
        assert "2+2?" in capsys.readouterr().out

    def test_extract_mcp_content_envelope(self):
        import fei_tpu.ui.cli as cli

        rows = cli._extract_search_results(
            {"content": [{"type": "text", "text": "Title — example.com"}]}
        )
        assert rows and "example.com" in rows[0]["description"]


class TestHistoryLoad:
    def test_load_replays_into_conversation(self, tmp_home, capsys, monkeypatch):
        import fei_tpu.ui.cli as cli

        monkeypatch.setattr(
            cli, "HISTORY_FILE",
            str(tmp_home / "history.json"),
        )
        hist = cli.History(str(tmp_home / "history.json"))
        hist.add("what is a mesh?", "a named device grid")

        args = cli.parse_args(["--provider", "mock", "history", "load", "0"])
        # avoid entering the interactive loop: stub chat_loop
        monkeypatch.setattr(cli, "chat_loop", lambda assistant, history: 0)
        captured = {}

        real_build = cli.build_assistant

        def spy_build(a):
            assistant = real_build(a)
            captured["assistant"] = assistant
            return assistant

        monkeypatch.setattr(cli, "build_assistant", spy_build)
        rc = cli.handle_history_command(args)
        assert rc == 0
        out = capsys.readouterr().out
        assert "what is a mesh?" in out
        msgs = captured["assistant"].conversation.messages
        assert msgs[0]["role"] == "user"
        assert msgs[1]["role"] == "assistant"

    def test_load_bad_index(self, tmp_home, monkeypatch):
        import fei_tpu.ui.cli as cli

        monkeypatch.setattr(cli, "HISTORY_FILE", str(tmp_home / "h.json"))
        args = cli.parse_args(["history", "load", "7"])
        assert cli.handle_history_command(args) == 1


class TestCLIStats:
    def test_stats_flag_prints_summary(self, capsys, tmp_path, monkeypatch):
        import fei_tpu.ui.cli as cli

        monkeypatch.setattr(cli, "HISTORY_FILE", str(tmp_path / "h.json"))
        rc = cli.main(
            ["--provider", "mock", "--no-stream", "--stats", "--message", "hi"]
        )
        err = capsys.readouterr().err
        assert rc == 0
        assert "-- stats" in err and "tokens:" in err
