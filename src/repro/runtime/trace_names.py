"""Names of the program's host spans and device scopes, in one module that
imports nothing, so that the engine, the train loop and whatever reads a
profiler trace of them share one spelling.

Host spans are ``jax.profiler.TraceAnnotation``\\ s: they land on the
profiler's host plane, on the clock of the device's ops. Their attributes
are host values only. Spans of one request carry its ``rid``; nesting on
the main thread gives each span its parent.

Device scopes are ``jax.named_scope``\\ s: each becomes a component of the
``op_name`` metadata of the ops traced inside it (wrapped as ``jvp(...)``
or ``transpose(jvp(...))`` where it is differentiated), which the
profiler's trace carries beside each op.
"""

# ------------------------------------------------------------- host spans
# the paged pool and page allocator made at the start of a run
POOL_INIT = "engine.pool_init"
# one iteration of the scheduler loop; step
STEP = "engine.step"
# deadline reaping of queued and active requests
REAP = "engine.reap"
# one admitted request: prefill, first token, lane admission;
# rid, prompt_len, cached (prompt tokens found in the prefix cache)
ADMIT = "engine.admit"
# one prefill chunk's dispatch and the wait for its logits; rid, start, tokens
PREFILL_CHUNK = "engine.prefill_chunk"
# one fused decode step: dispatch, wait, lane-state read; step, lanes
DECODE = "engine.decode"
# inside DECODE: the host copies of the lanes' active flags and counts
LANE_STATE_READ = "engine.lane_state_read"
# one finished lane: its tokens read and its pages released; rid, tokens
RETIRE = "engine.retire"
# the engine idle until the next arrival
WAIT_FOR_ARRIVAL = "engine.wait_for_arrival"
# the train loop fetching a step's rows; step
TRAIN_BATCH = "train.batch"
# a train step from dispatch to its loss on the host; step
TRAIN_STEP = "train.step"
# the StepTraceAnnotation around TRAIN_STEP
TRAIN_STEP_GROUP = "train"

SPAN_PREFIXES = ("engine.", "train.")

# ---------------------------------------------------------- device scopes
EMBED = "embed"              # token embedding and positions
LAYERS = "layers"            # each scan over the layer stack
NORM = "norm"                # every RMS or layer norm
ATTENTION = "attention"      # QKV projection, RoPE, attention, output proj.
KV_WRITE = "kv_write"        # writes of new K/V into the paged pool
MLP = "mlp"
MOE = "moe"
LM_HEAD = "lm_head"          # logits over the vocabulary
LOSS = "loss"                # log-softmax and mean NLL
SAMPLE = "sample"            # sampling and lane bookkeeping of a decode step
OPTIMIZER = "optimizer"      # AdamW update and the global gradient norm

SCOPES = (EMBED, LAYERS, NORM, ATTENTION, KV_WRITE, MLP, MOE, LM_HEAD, LOSS,
          SAMPLE, OPTIMIZER)
