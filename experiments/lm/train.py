"""Language-model training entrypoint: the flagship transformer end to end.

No reference counterpart (the reference stops at MLP/ConvNet classifiers,
SURVEY.md §2.3); this is the long-context / multi-axis showcase:

- flash attention kernels auto-enable on TPU (``--attention`` overrides);
- ``--experts N`` switches the FFNs to capacity-dispatch MoE (EP-shardable);
- ``--mesh data=2,model=2,...`` trains over an explicit multi-axis mesh with
  the Megatron TP rule table;
- checkpoints (``--checkpoint-dir``) use the versioned store with resume.

The corpus is a deterministic Markov byte stream (experiments/lm/data.py):
final perplexity far below the unigram baseline == the model really learned
the transition structure (ideal is ~branching, default 8).

Run:  python -m experiments.lm.train --steps 200 --seq 512
"""

from __future__ import annotations

import argparse
import os
import sys

import jax
import numpy as np

from distriflow_tpu.models.transformer import (
    TransformerConfig,
    pipelined_transformer_lm,
    transformer_lm,
)
from distriflow_tpu.parallel import create_mesh, data_parallel_mesh
from distriflow_tpu.parallel.sharding import (
    PIPELINED_TRANSFORMER_RULES,
    TRANSFORMER_TP_RULES,
)
from distriflow_tpu.train.sync import SyncTrainer
from distriflow_tpu.train.loop import run_chunked
from distriflow_tpu.utils.compile_cache import enable_compile_cache
from distriflow_tpu.utils.config import MeshConfig

from experiments.lm.data import VOCAB, batches, generate_corpus


def parse_mesh(spec: str):
    if not spec:
        return data_parallel_mesh()
    axes = dict(kv.split("=") for kv in spec.split(","))
    return create_mesh(MeshConfig(**{k: int(v) for k, v in axes.items()}))


def main(argv=None) -> float:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq", type=int, default=512)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--n-layers", type=int, default=4)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--d-ff", type=int, default=1024)
    p.add_argument("--experts", type=int, default=0)
    p.add_argument("--attention", choices=("auto", "flash", "blockwise", "ring", "ulysses"),
                   default="auto")
    p.add_argument("--dtype", choices=("bfloat16", "float32"), default="bfloat16")
    p.add_argument("--loss", default=None,
                   help="loss registry name (default auto: the Pallas fused "
                        "sparse CE on TPU, optax sparse CE elsewhere)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize blocks in backward (long-context memory)")
    p.add_argument("--pipeline-schedule", choices=("gpipe", "remat", "1f1b"),
                   default=None,
                   help="PP backward schedule (mesh must include pipe=N>1)")
    p.add_argument("--mesh", default="", help="e.g. data=2,model=2,seq=2")
    p.add_argument("--learning-rate", type=float, default=3e-3)
    p.add_argument("--steps-per-dispatch", type=int, default=1,
                   help="run K optimizer steps per device dispatch "
                        "(lax.scan via SyncTrainer.step_many) — amortizes "
                        "host/transport latency, which dominates small-model "
                        "wall clock; loss prints once per chunk")
    p.add_argument("--corpus-tokens", type=int, default=200_000)
    p.add_argument("--tokens-file", default=None,
                   help="train from a real memmapped token file "
                        "(write_token_file format); the last ~10%% of the "
                        "file's windows are HELD OUT for eval — training "
                        "never sees them")
    p.add_argument("--vocab-size", type=int, default=None,
                   help="model vocab (default: the synthetic corpus vocab; "
                        "REQUIRED to cover the token ids in --tokens-file)")
    p.add_argument("--zero-level", type=int, default=0, choices=(0, 1, 2),
                   help="ZeRO memory sharding over the data axis: 1 = adam "
                        "moments, 2 = gradients+EMA reduce-scattered too")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--save-every", type=int, default=0)
    p.add_argument("--generate", type=int, default=0,
                   help="after training, decode N tokens from a corpus prompt "
                        "and report how many follow the Markov structure")
    def host_port(value: str):
        # validate at parse time: a typo must not cost the training run
        host, _, port = value.rpartition(":")
        try:
            return host or "127.0.0.1", int(port or 0)
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"expected HOST:PORT or :0, got {value!r}"
            )

    p.add_argument("--serve", metavar="HOST:PORT", default=None, type=host_port,
                   help="after training, serve the model for remote "
                        "generate/beam-search (InferenceServer) until "
                        "interrupted; HOST:PORT or :0 for an ephemeral port")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    enable_compile_cache()

    import jax.numpy as jnp

    gen_prompt_len = min(32, args.seq)
    if args.generate and gen_prompt_len + args.generate > args.seq:
        # fail BEFORE training, not after the run's budget is spent
        p.error(
            f"--generate {args.generate} + prompt {gen_prompt_len} exceeds "
            f"--seq {args.seq} (the decode cache length)"
        )

    mesh = parse_mesh(args.mesh)
    cfg = TransformerConfig(
        vocab_size=args.vocab_size or VOCAB,
        d_model=args.d_model,
        n_heads=args.n_heads,
        n_layers=args.n_layers,
        d_ff=args.d_ff,
        max_seq=args.seq,
        n_experts=args.experts,
        dtype=jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32,
        use_flash_attention={"auto": None, "flash": True}.get(args.attention, False),
        use_ring_attention=args.attention == "ring",
        use_ulysses_attention=args.attention == "ulysses",
        remat=args.remat,
        pipeline_schedule=args.pipeline_schedule,
        loss=args.loss,
    )
    # a pipe axis in --mesh selects the GPipe-staged model (DP x PP x TP);
    # --pipeline-schedule then picks the backward schedule
    pipelined = mesh.shape.get("pipe", 1) > 1
    if pipelined:
        if args.generate or args.serve:
            # fail BEFORE training: decode/serving consume transformer_lm's
            # flat param tree, not the stage-stacked pipelined layout
            raise SystemExit(
                "--generate/--serve do not support the pipelined layout "
                "(pipe=N in --mesh); train pipelined, or drop the pipe axis "
                "for a decode-capable run"
            )
        spec = pipelined_transformer_lm(cfg, mesh=mesh, example_seq=args.seq)
    else:
        if args.pipeline_schedule:
            raise SystemExit("--pipeline-schedule needs pipe=N>1 in --mesh")
        spec = transformer_lm(cfg, mesh=mesh, example_seq=args.seq)
    trainer = SyncTrainer(
        spec, mesh=mesh, learning_rate=args.learning_rate, optimizer="adam",
        param_rules=PIPELINED_TRANSFORMER_RULES if pipelined else TRANSFORMER_TP_RULES,
        verbose=True, zero_level=args.zero_level,
        checkpoint_dir=args.checkpoint_dir, save_every=args.save_every,
    )
    trainer.init(jax.random.PRNGKey(args.seed))
    start_step = 0
    if args.checkpoint_dir and trainer.restore():
        start_step = trainer.version
        print(f"resumed at step {start_step}", file=sys.stderr)

    stream_ds = eval_ds = None
    if args.tokens_file:
        # real corpus: memmapped windows with a REAL holdout — the last 10%
        # of windows (>= one batch) are eval-only; training never sees them
        from distriflow_tpu.data import StreamingTokenDataset

        probe = StreamingTokenDataset(
            args.tokens_file, seq_len=args.seq, batch_size=args.batch_size,
            seed=args.seed)
        # fail BEFORE training on out-of-vocab ids anywhere in the FILE
        # (a silent overflow would index the embedding with garbage)
        max_id = probe.max_token_id()
        if max_id >= cfg.vocab_size:
            raise SystemExit(
                f"--tokens-file contains id {max_id} >= model vocab "
                f"{cfg.vocab_size}; pass --vocab-size >= {max_id + 1}"
            )
        total = probe.n_windows
        # each side needs one full batch PER PROCESS (the dataset shards
        # windows across processes before flooring to whole batches)
        per_side = probe.process_count * args.batch_size
        split = total - max(total // 10, per_side)
        if split < per_side:
            raise SystemExit(
                f"--tokens-file has only {total} windows of seq {args.seq}: "
                f"a train/eval split needs >= {2 * per_side} "
                f"({probe.process_count} process(es) x batch {args.batch_size} "
                "per side)"
            )
        stream_ds = StreamingTokenDataset(
            args.tokens_file, seq_len=args.seq, batch_size=args.batch_size,
            seed=args.seed, window_range=(0, split))
        eval_ds = StreamingTokenDataset(
            args.tokens_file, seq_len=args.seq, batch_size=args.batch_size,
            seed=args.seed, window_range=(split, total))
        if start_step:
            # exact cursor resume with no sidecar state: consumption is one
            # batch per optimizer step and the epoch order is a pure
            # function of (seed, epoch) — seek to the restored step
            stream_ds.seek(start_step)
            print(f"stream cursor sought to epoch {stream_ds.epoch} "
                  f"batch {stream_ds.batch_in_epoch}", file=sys.stderr)
        stream = iter(stream_ds)
        corpus = eval_corpus = None
    else:
        corpus = generate_corpus(args.corpus_tokens, seed=args.seed)
        # train on the head, hold out the tail for eval — random training
        # offsets never enter the held-out slice
        split = max(len(corpus) - max(4 * (args.seq + 1), len(corpus) // 10),
                    args.seq + 2)
        train_corpus, eval_corpus = corpus[:split], corpus[split:]
        stream = batches(train_corpus, args.batch_size, args.seq, args.steps,
                         args.seed + start_step)
    # one device dispatch per --steps-per-dispatch steps (run_chunked:
    # steady-state timing, full chunks only); seed by the resumed step so a
    # restarted run continues the batch stream instead of replaying windows
    res = run_chunked(
        trainer,
        stream,
        steps=args.steps,
        steps_per_dispatch=args.steps_per_dispatch,
        log=lambda s, l: print(
            f"step {start_step + s} loss {l:.4f}", file=sys.stderr),
    )
    note = res.tail_note(args.steps)
    if note:
        print(note, file=sys.stderr)
    # steady-state only: runs that fit in one dispatch have no timed steps
    tok_s = res.steps_per_sec * args.batch_size * args.seq

    # held-out eval (aux-free, jitted via the trainer); with the synthetic
    # corpus, compare against the context-free unigram baseline
    if args.tokens_file:
        ex, ey = next(iter(eval_ds))  # held-out windows: never trained on
        (eval_loss,) = (float(v) for v in trainer.evaluate(ex, ey, metrics=("loss",)))
        print(
            f"lm: {tok_s:,.0f} tok/s | eval loss {eval_loss:.4f} "
            f"(ppl {np.exp(eval_loss):.1f}) [held-out stream windows]",
            file=sys.stderr,
        )
    else:
        ex, ey = next(batches(eval_corpus, args.batch_size, args.seq, 1, args.seed + 99))
        (eval_loss,) = (float(v) for v in trainer.evaluate(ex, ey, metrics=("loss",)))
        counts = np.bincount(corpus, minlength=VOCAB).astype(np.float64)
        probs = counts / counts.sum()
        unigram = float(-(probs[probs > 0] * np.log(probs[probs > 0])).sum())
        print(
            f"lm: {tok_s:,.0f} tok/s | eval loss {eval_loss:.4f} "
            f"(ppl {np.exp(eval_loss):.1f}) vs unigram {unigram:.4f} "
            f"(ppl {np.exp(unigram):.1f})",
            file=sys.stderr,
        )
    params = trainer.get_params()
    if (args.generate or args.serve is not None) and mesh.devices.size > 1:
        # decode and serve from ONE chip — a one-chip replica. A Mosaic
        # kernel lowers in a multi-device program only inside a shard_map,
        # and the decode kernels' multi-device wrappers rely on
        # custom_partitioning, which the installed jax/libtpu does not
        # carry to the TPU compiler (PR 21: every such program failed on
        # four real chips). Multi-chip serving is ROADMAP R7.
        params = jax.device_put(params, jax.devices()[0])
    if args.generate > 0:
        from distriflow_tpu.models import generate as lm_generate

        prompt_src = eval_corpus if eval_corpus is not None else np.asarray(ex[0])
        prompt = jnp.asarray(prompt_src[None, :gen_prompt_len], jnp.int32)
        out = lm_generate(cfg, params, prompt, args.generate)
        gen = np.asarray(out[0, gen_prompt_len:])
        if corpus is None:
            print(f"generated {args.generate} tokens", file=sys.stderr)
        else:
            # a correct continuation only ever takes transitions that occur
            # in the corpus; measure the fraction of generated bigrams that do
            seen = set(zip(corpus[:-1].tolist(), corpus[1:].tolist()))
            pairs = list(zip(np.asarray(out[0, 31:-1]).tolist(), gen.tolist()))
            valid = sum(p in seen for p in pairs) / len(pairs)
            print(f"generated {args.generate} tokens; {valid:.0%} of transitions "
                  f"follow the corpus Markov structure", file=sys.stderr)
    if args.serve is not None:
        from distriflow_tpu.server import InferenceServer

        host, port = args.serve
        server = InferenceServer(
            cfg, params, host=host, port=port, verbose=True,
        ).setup()
        print(f"serving inference on {server.address} — Ctrl-C to stop",
              file=sys.stderr, flush=True)
        try:
            import threading

            threading.Event().wait()
        except KeyboardInterrupt:
            pass
        finally:
            server.stop()
    trainer.close()
    return eval_loss


if __name__ == "__main__":
    main()
