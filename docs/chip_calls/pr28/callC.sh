#!/bin/bash
# the two cells that exist, parent against change, one seed a pair
run() { # dir tag workload seed
  ( cd $1 && s=$(date +%s); python3 benchmark/run.py --workload $3 --seed $4 --seconds 45 --trace 0 > $OLDPWD/chiprun_out/C_$2.out 2> $OLDPWD/chiprun_out/C_$2.err; echo "C_$2 rc=$? wall $(( $(date +%s) - s )) s"; tail -1 $OLDPWD/chiprun_out/C_$2.out | cut -c1-900 )
}
run .scratch/parent ft_parent bert_base_cls.finetune_doc512 2800000211
run . ft_change bert_base_cls.finetune_doc512 2800000211
run . sv_change bert_base_cls.serve_doc512_c256 2800000223
run .scratch/parent sv_parent bert_base_cls.serve_doc512_c256 2800000223
