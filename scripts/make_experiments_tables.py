"""Generate the §Workflow-DAG markdown table from BENCH_dag_scale.json."""
import json
import os

dag_path = "BENCH_dag_scale.json"
if os.path.exists(dag_path):
    with open(dag_path) as f:
        d = json.load(f)
    print("### Workflow-DAG partitioning "
          f"({d['stages']} stages x K={d['channels']}; joint solve vs "
          "stage-by-stage greedy)\n")
    print("| method | E[makespan] | Var[makespan] | realized E[makespan] "
          "(paired MC) | solve ms |")
    print("|---|---|---|---|---|")
    times = {e["name"]: e["median_us"] / 1e3 for e in d["entries"]}
    for name, key in (("greedy (per-stage)", "greedy"), ("joint", "joint")):
        m = d[key]
        t = times.get(f"{key}_solve_xla")
        tstr = f"{t:.0f}" if t is not None else "-"
        print(f"| {name} | {m['makespan_mu']:.4f} | {m['makespan_var']:.6f} "
              f"| {m['mc_makespan_mu']:.4f} | {tstr} |")
    print(f"\njoint improvement: {d['improvement_pct']:.3f}% expected "
          f"(realized {d['realized_improvement_pct']:.3f}%), "
          f"{d['family_groups']} stacked kernel launch(es) per moment "
          "evaluation")
