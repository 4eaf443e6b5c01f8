"""Share of its roofline that the Pallas flash-attention forward reaches,
in %: for each of its calls in the traced window, the least time its
shapes allow (the larger of the causal FLOPs at the bf16 peak and the
bytes of q, k, v, out and lse at the HBM peak, ``flops.flash_call``), summed
and divided by the summed device time of those calls.  The calls are the
custom calls that return the kernel's f32 log-sum-exp ``[B*H, 1, T]``."""


def read(run):
    cfg, mix, peak = run["config"], run["mix"], run["peak"]
    heads = cfg["num_attention_heads"]
    hd = cfg.get("head_dim", cfg["hidden_size"] // heads)
    batch = mix["batch"] // run["chips"]
    seq = mix["seq"]
    lo, hi = run["window"]
    sig = f"f32[{batch * heads},1,{seq}]"
    events = [e for e in run["devtrace"].kernel_events(run["trace"], sig, lo, hi)
              if "custom-call(" in e[0]]
    if not events:
        return None
    fl, nb = run["flops"].flash_call(batch, seq, heads,
                                     cfg["num_key_value_heads"], hd)
    least = max(fl / peak["flops_s"], nb / peak["hbm_bytes_s"])
    spent = sum(e - s for _, s, e in events)
    return 100.0 * least * len(events) / spent
