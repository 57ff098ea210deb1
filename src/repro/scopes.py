"""Names of the layers of the MARL training step.

Each name is a ``jax.named_scope`` placed where the work happens, so every
HLO instruction of the compiled step carries the layer it belongs to in
its ``op_name`` metadata (``.../rollout/while/body/closed_call/policy/
lstm/dot_general``; the backward under ``transpose(jvp())``). A profiler
trace names device operations by instruction, so the compiled text maps
each operation of a trace to a layer. Scopes change metadata only: the
compiled program is the same with or without them.
"""
ROLLOUT = "rollout"            # the step scan of one episode
ENV = "env"                    # reset, observe, step, the done freeze, success
POLICY = "policy"              # one IC3Net forward step, all agents
COMM = "comm"                  # communication projection and gated mean
ENCODER = "encoder"            # observation encoder
LSTM = "lstm"                  # the LSTM cell
HEADS = "heads"                # action, value and gate heads
SAMPLE = "sample"              # key split, action and gate sampling, log-probs
A2C = "a2c"                    # returns, advantage and the loss
RMSPROP = "rmsprop"            # the optimizer update
PLAN_REFRESH = "plan_refresh"  # FLGW plan re-encode (grouped path only)

LAYER_SCOPES = (ROLLOUT, ENV, POLICY, COMM, ENCODER, LSTM, HEADS, SAMPLE,
                A2C, RMSPROP, PLAN_REFRESH)
