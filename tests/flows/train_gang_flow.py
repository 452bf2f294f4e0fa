"""North-star path: `num_parallel` gang step training a
Llama model with jax.distributed — each rank is one process of a multi-host
JAX program; the mesh spans all ranks' devices (SURVEY.md §2.9)."""

from metaflow_tpu import FlowSpec, current, step


class TrainGangFlow(FlowSpec):
    @step
    def start(self):
        self.seed = 0
        self.next(self.train, num_parallel=2)

    @step
    def train(self):
        # TpuParallelDecorator (auto-attached) has already called
        # jax.distributed.initialize: this process is one host of the gang
        import jax

        assert jax.process_count() == 2, jax.process_count()
        from metaflow_tpu.models import llama
        from metaflow_tpu.spmd import MeshSpec, create_mesh
        from metaflow_tpu.training import (
            default_optimizer,
            make_trainer,
            shard_batch,
        )

        cfg = llama.LlamaConfig.tiny()
        mesh = create_mesh(MeshSpec.fsdp())  # spans BOTH processes' devices
        self.global_devices = len(jax.devices())
        state, step_fn, _ = make_trainer(
            jax.random.PRNGKey(self.seed), cfg, mesh, llama,
            optimizer=default_optimizer(lr=1e-2, warmup_steps=1,
                                        total_steps=50),
        )
        batch_size = max(4, self.global_devices)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (batch_size, 33), 0, cfg.vocab_size
        )
        batch = shard_batch({"tokens": tokens}, mesh)
        with mesh:
            losses = []
            for _ in range(3):
                state, m = step_fn(state, batch)
                losses.append(float(m["loss"]))
        self.losses = losses
        self.rank = current.parallel.node_index
        self.next(self.join)

    @step
    def join(self, inputs):
        all_losses = [inp.losses for inp in inputs]
        self.devices = {inp.rank: inp.global_devices for inp in inputs}
        # every rank ran the SAME global program: losses must agree
        assert all(l == all_losses[0] for l in all_losses), all_losses
        self.final_loss = all_losses[0][-1]
        self.first_loss = all_losses[0][0]
        self.next(self.end)

    @step
    def end(self):
        assert self.final_loss < self.first_loss
        counts = set(self.devices.values())
        assert len(counts) == 1 and counts.pop() >= 2, self.devices
        print("gang training ok: loss %.3f -> %.3f on %s"
              % (self.first_loss, self.final_loss, self.devices))


if __name__ == "__main__":
    TrainGangFlow()
