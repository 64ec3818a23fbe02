"""Grammar-enforced tool calls: the decoder cannot emit an invalid call.

The reference validates tool-call JSON after the fact
(fei/tools/registry.py:92-153) and silently drops what fails to parse.
Here the union grammar over every registered tool's input schema drives
generation the moment the model emits the <tool_call> trigger — on the
dense path the DFA steps inside the fused on-device scan; on the paged
path it rides the batched scheduler step with per-slot states.

Runs hermetically on CPU with random tiny weights — which is exactly the
point: even a model emitting pure noise produces a schema-valid call.

    JAX_PLATFORMS=cpu python examples/constrained_tool_calls.py
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json

from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.engine.grammar import char_walk, compile_agent_tool_grammar
from fei_tpu.utils.metrics import METRICS

TOOLS = [
    {
        "name": "GrepTool",
        "description": "search file contents",
        "input_schema": {
            "type": "object",
            "properties": {
                "pattern": {"type": "string"},
                "path": {"type": "string"},
            },
            "required": ["pattern"],
        },
    },
    {
        "name": "Shell",
        "description": "run a command",
        "input_schema": {
            "type": "object",
            "properties": {"command": {"type": "string"}},
            "required": ["command"],
        },
    },
]


def main() -> None:
    engine = InferenceEngine.from_config("tiny")
    grammar = compile_agent_tool_grammar(TOOLS, engine.tokenizer)
    print(
        f"union grammar over {len(TOOLS)} tools: "
        f"{grammar.table.shape[0]} DFA states, "
        f"{grammar.table_bytes / 1e6:.2f} MB token tables, "
        f"lifted in {grammar.lift_seconds:.2f}s"
    )

    # use the model's own first token as the trigger so the constrained
    # phase engages deterministically under random weights (a real
    # checkpoint emits the taught <tool_call> tag instead)
    gen = GenerationConfig(max_new_tokens=96, ignore_eos=True)
    prompt = list(range(11, 23))
    first = next(iter(engine.generate_stream(prompt, gen)))
    trigger = engine.tokenizer.decode([first])

    toks = list(
        engine.generate_stream_toolcalls(
            prompt, gen, grammar=grammar, trigger=trigger
        )
    )
    text = engine.tokenizer.decode(toks)
    payload = text[len(trigger):-len("</tool_call>")]
    call = json.loads(payload)  # grammar guarantee: always parses
    assert char_walk(grammar, payload) == grammar.accept

    fused = METRICS.snapshot()["counters"].get("engine.grammar_fused_steps", 0)
    print(f"model emitted (random weights!): {payload}")
    print(f"tool: {call['name']}  arguments: {call['arguments']}")
    print(f"fused on-device DFA steps: {fused:.0f} — zero per-token host syncs")


if __name__ == "__main__":
    main()
