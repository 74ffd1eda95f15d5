"""Names of the serving engine's host spans and the paged decode step's
device scopes, shared by the program and by whatever reads its traces.

Host spans are ``jax.profiler.TraceAnnotation``s: they land on the
profiler's host plane, on the same clock as the device's op events,
and cost a few hundred ns when no profiler runs.  ``RUN`` covers one
``ServeEngine.run()``; the others are its steps, once per super-bucket
(per wave for the flash tier's spill and fault-in).

Device scopes are ``jax.named_scope``s: HLO ``op_name`` metadata only,
no runtime cost.  ``ATTN`` holds two child scopes, so an op under them
carries the path ``decode.attn/kv_write`` or ``decode.attn/attn_read``.
The ``lax.scan`` over layers and the decode ``while_loop``'s carry are
left unscoped on purpose: what XLA adds to move the stacked KV pool
(slices, relayouts, carry copies) is the loop's unscoped time.
"""

RUN = "serve.run"                    # the whole ServeEngine.run()
ADMIT = "serve.admit"                # bucket geometry, page plan, host->device puts
PREFILL = "serve.prefill"            # the prefill dispatch (and its FRAC fake-quant)
POOL_FILL = "serve.pool_fill"        # prompt KV scattered into the page pool
FIRST_TOKEN_SYNC = "serve.first_token_sync"   # wait for prefill's first tokens
LOOP = "serve.loop"                  # decode loop dispatch + its single device_get
FINISH = "serve.finish"              # page bookkeeping, results, meter booking
SPILL = "serve.spill"                # flash tier: staged prompt KV out to flash
FAULT_IN = "serve.fault_in"          # flash tier: a wave's prompt KV back in

HOST = (RUN, ADMIT, PREFILL, POOL_FILL, FIRST_TOKEN_SYNC, LOOP, FINISH,
        SPILL, FAULT_IN)

ATTN = "decode.attn"                 # norm, QKV, rope, output projection
KV_WRITE = "kv_write"                # FRAC fake-quant + the page write
ATTN_READ = "attn_read"              # the paged kernel or the gather read
MLP = "decode.mlp"
HEAD = "decode.head"                 # final norm, LM head, greedy sample
LOOP_ALLOC = "loop.alloc"            # on-demand page allocation
LOOP_EMIT = "loop.emit"              # tokens into request rows, lane liveness
LOOP_ADMIT = "loop.admit"            # free dead lanes' pages, admit staged requests

KV_WRITE_PATH = f"{ATTN}/{KV_WRITE}"
ATTN_READ_PATH = f"{ATTN}/{ATTN_READ}"

DEVICE = (ATTN, KV_WRITE_PATH, ATTN_READ_PATH, MLP, HEAD,
          LOOP_ALLOC, LOOP_EMIT, LOOP_ADMIT)
