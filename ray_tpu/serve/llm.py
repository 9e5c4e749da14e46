"""An LLM serve replica that owns its model.

`models.serving.LLMDeployment(params, cfg)` closes over weights the DRIVER
built — fine on a CPU, wrong on a chip: a driver that has initialised a
backend holds the chip, and the replica that needs it then fails or hangs.
`LLMReplica` is described by plain values (a preset name, a seed, sizes) and
builds config, weights and engine in `__init__`, i.e. in the replica's own
process. This module imports no jax, so a launcher can stay off it.

    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMReplica
    D = serve.deployment(LLMReplica, name="LLM",
                         ray_actor_options={"resources": {"TPU": 1}})
    serve.run(D.bind(preset="b1", seed=0, num_slots=8, max_len=512))
"""

from __future__ import annotations


class LLMReplica:
    def __init__(self, preset: str = "tiny", *, seed: int = 0,
                 num_slots: int = 4, max_len: int = 256):
        """`preset` names a static constructor of `ModelConfig` (`tiny`,
        `b1`: the dense block) or of `HybridConfig` (`tiny_hybrid`: KDA and
        MLA mixers, dropless experts; `tiny_runs`: Mamba and attention mixers
        as scanned runs; `tiny_granite`: Mamba-2 and attention mixers over
        scanned expert layers); the engine is the same class."""
        import jax

        from ray_tpu.models import ModelConfig, hybrid, init_params
        from ray_tpu.models.serving import ContinuousBatchingEngine

        if hasattr(ModelConfig, preset):
            self.cfg, init = getattr(ModelConfig, preset)(), init_params
        else:
            self.cfg = getattr(hybrid.HybridConfig, preset)()
            init = hybrid.init_params
        self.params = init(jax.random.PRNGKey(seed), self.cfg)
        self.engine = ContinuousBatchingEngine(
            self.params, self.cfg, num_slots=num_slots, max_len=max_len)

    def __serve_start__(self):
        self.engine.start_driver()

    def __serve_stop__(self):
        self.engine.stop_driver()

    def __call__(self, payload):
        return self.engine.generate(
            list(payload["prompt"]),
            max_new_tokens=int(payload.get("max_new_tokens", 32)))

    def stream(self, payload):
        """Streaming entry (`POST /<name>/stream?stream=1`, or a handle with
        `options(method_name="stream", stream=True)`): tokens as decoded."""
        yield from self.engine.generate_stream(
            list(payload["prompt"]),
            max_new_tokens=int(payload.get("max_new_tokens", 32)))
