"""The loop step a token is expected to leave a looped model at, ``sum_t t
p_t`` averaged over the tokens of the step-0 check's sequence (1 ..
``loop_steps``): counted by the program from its exit gates
(``make_lm_train_step``'s ``loop_exit_mean_step``). Training pays every step
whatever it reads; it says how much of the loop an inference-time exit rule
at this gate would run. A model without an exit gate gives None."""


def read(run):
    return run.get("client", {}).get("check", {}).get("loop_exit_mean_step")
